"""DLRM (Naumov et al.) — the paper's model (§II-A, Fig. 2; config from §V).

Stages: Bottom MLP (continuous features) | Embedding stage (categorical) |
Feature interaction | Top MLP -> CTR logit.

Interactions: `dot`, the pairwise dot products (the paper's); `cat`, the
joined features; `dcn`, DCN V2's low-rank cross network (Wang et al.,
arXiv:2008.13535) over the joined features, as MLPerf's DLRM-DCNv2 uses
it (TorchRec `LowRankCrossNet`): x_0 is the bottom output and the pooled
rows, dense first, flattened to (T+1)·D features, and each of
`dcn_layers` layers computes x_{l+1} = x_0 ⊙ (U_l (V_l x_l) + b_l) + x_l,
V_l mapping (T+1)·D to `dcn_rank` with no bias, U_l mapping back with
bias b_l. The stages run under `jax.named_scope` (`bottom`, `embedding`,
`interaction` or `cross`, `top`), so the profiler's op metadata names the
stage of each device op.

The embedding stage is an EmbeddingBagCollection (core/embedding.py) — the
paper's technique (prefetch-pipelined, VMEM-pinned gather kernel) plugs in
through its EmbeddingStageConfig.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.embedding import EmbeddingBagCollection, EmbeddingStageConfig
from repro.models.layers import dense_init, mlp_tower_apply, mlp_tower_init


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    # paper §V defaults
    dense_features: int = 13
    bottom_mlp: tuple[int, ...] = (1024, 512, 128, 128)
    top_mlp: tuple[int, ...] = (128, 64, 1)
    embedding: EmbeddingStageConfig = EmbeddingStageConfig()
    interaction: str = "dot"      # dot | cat | dcn
    dtype: str = "float32"
    # the `dcn` interaction's cross layers and their rank (MLPerf's
    # DLRM-DCNv2: 3 layers of rank 512)
    dcn_layers: int = 3
    dcn_rank: int = 512

    def __post_init__(self):
        if self.interaction not in ("dot", "cat", "dcn"):
            raise ValueError(f"unknown interaction {self.interaction!r}: "
                             f"dot, cat or dcn")
        if self.interaction == "dcn" and min(self.dcn_layers,
                                             self.dcn_rank) < 1:
            raise ValueError("the dcn interaction needs dcn_layers >= 1 "
                             "and dcn_rank >= 1")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def interaction_dim(self) -> int:
        t = self.embedding.num_tables + 1      # +1: bottom MLP output
        if self.interaction == "dot":
            return self.bottom_mlp[-1] + t * (t - 1) // 2
        return self.bottom_mlp[-1] * t


class DLRM:
    def __init__(self, cfg: DLRMConfig, plans=None):
        assert cfg.bottom_mlp[-1] == cfg.embedding.dim, \
            "bottom MLP output must match embedding dim for dot interaction"
        self.cfg = cfg
        self.ebc = EmbeddingBagCollection(cfg.embedding, plans)

    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        params = {
            "bottom": mlp_tower_init(
                k1, (cfg.dense_features, *cfg.bottom_mlp), cfg.jnp_dtype),
            "embedding": self.ebc.init(k2),
            "top": mlp_tower_init(
                k3, (self.cfg.interaction_dim(), *cfg.top_mlp), cfg.jnp_dtype),
        }
        if cfg.interaction == "dcn":
            params["cross"] = self._cross_init(k4)
        return params

    def _cross_init(self, rng: jax.Array) -> dict:
        """Per layer l: v{l} [F, rank], u{l} [rank, F], b{l} [F], with F
        the joined features."""
        cfg = self.cfg
        f, r = cfg.interaction_dim(), cfg.dcn_rank
        out = {}
        for layer in range(cfg.dcn_layers):
            kv, ku = jax.random.split(jax.random.fold_in(rng, layer))
            out[f"v{layer}"] = dense_init(kv, (f, r), cfg.jnp_dtype)
            out[f"u{layer}"] = dense_init(ku, (r, f), cfg.jnp_dtype)
            out[f"b{layer}"] = jnp.zeros((f,), cfg.jnp_dtype)
        return out

    def _interact(self, params: dict, bottom_out: jnp.ndarray,
                  pooled: jnp.ndarray):
        """bottom_out: [B, D]; pooled: [B, T, D] -> interaction features."""
        cfg = self.cfg
        feats = jnp.concatenate([bottom_out[:, None, :], pooled], axis=1)
        if cfg.interaction == "dot":
            gram = jnp.einsum("btd,bsd->bts", feats, feats)  # [B, T+1, T+1]
            t = feats.shape[1]
            iu, ju = jnp.triu_indices(t, k=1)
            pairs = gram[:, iu, ju]                          # [B, C(T+1,2)]
            return jnp.concatenate([bottom_out, pairs], axis=1)
        b = feats.shape[0]
        x0 = feats.reshape(b, -1)
        if cfg.interaction == "cat":
            return x0
        cross, x = params["cross"], x0
        for layer in range(cfg.dcn_layers):
            x = x0 * ((x @ cross[f"v{layer}"]) @ cross[f"u{layer}"]
                      + cross[f"b{layer}"]) + x
        return x

    def forward(self, params: dict, dense: jnp.ndarray,
                sparse_indices: jnp.ndarray,
                sparse_weights: jnp.ndarray | None = None) -> jnp.ndarray:
        """dense: [B, F]; sparse_indices: [B, T, L] or flat
        [B, sum(L)] -> CTR logits [B]."""
        with jax.named_scope("embedding"):
            pooled = self.ebc.apply(params["embedding"], sparse_indices,
                                    sparse_weights)
        return self.forward_from_pooled(params, dense, pooled)

    def forward_from_pooled(self, params: dict, dense: jnp.ndarray,
                            pooled: jnp.ndarray) -> jnp.ndarray:
        """Everything after the embedding stage: pooled [B, T, D] -> logits.

        Split out so tiered storage can run the parameter-server lookup on
        the host and feed the pooled rows into this jitted remainder.
        """
        with jax.named_scope("bottom"):
            bottom = mlp_tower_apply(params["bottom"], dense, final_act=True)
        with jax.named_scope("cross" if self.cfg.interaction == "dcn"
                             else "interaction"):
            z = self._interact(params, bottom, pooled.astype(bottom.dtype))
        with jax.named_scope("top"):
            logit = mlp_tower_apply(params["top"], z)
        return logit[:, 0]

    def embedding_only(self, params: dict, sparse_indices: jnp.ndarray):
        """Embedding stage in isolation (paper's embedding-only latency)."""
        return self.ebc.apply(params["embedding"], sparse_indices)

    def loss(self, params, dense, sparse_indices, labels):
        logit = self.forward(params, dense, sparse_indices)
        return jnp.mean(
            jnp.maximum(logit, 0) - logit * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logit))))  # stable BCE-with-logits
