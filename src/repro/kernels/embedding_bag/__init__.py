from .fused import (FusedLookupOpts, FusedLookupResult, complete_miss_bags,
                    fused_warm_lookup, fused_warm_lookup_pallas,
                    fused_warm_lookup_xla)
from .kernel import (EmbeddingBagOpts, embedding_bag_flat_pallas,
                     embedding_bag_pallas)
from .ops import (embedding_bag, embedding_bag_flat, embedding_bag_stacked,
                  embedding_lookup)
from .ref import (embedding_bag_flat_ref, embedding_bag_ragged_ref,
                  embedding_bag_ref, embedding_lookup_ref)

__all__ = [
    "EmbeddingBagOpts", "embedding_bag_pallas", "embedding_bag",
    "embedding_bag_flat_pallas", "embedding_bag_flat",
    "embedding_bag_stacked", "embedding_bag_flat_ref",
    "embedding_lookup", "embedding_bag_ref", "embedding_bag_ragged_ref",
    "embedding_lookup_ref", "FusedLookupOpts", "FusedLookupResult",
    "fused_warm_lookup", "fused_warm_lookup_pallas", "fused_warm_lookup_xla",
    "complete_miss_bags",
]
