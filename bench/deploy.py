"""Builds the system under test from a configuration file: the DLRM, its
storage backend and a `ServingSession` over them. Everything the
benchmark takes from the program goes through here."""
from __future__ import annotations

import jax
import numpy as np


def _frozen(value):
    return tuple(value) if isinstance(value, list) else value


def model_config(cfg: dict):
    """The program's `DLRMConfig` for a configuration file. Lists (the
    towers; `rows` and `pooling` where they are given per table) go to
    the program as tuples. The optional objects `model_args` and
    `embedding_args` hold further keyword arguments of `DLRMConfig` and
    `EmbeddingStageConfig`, for fields the keys below do not name."""
    from repro.core.embedding import EmbeddingStageConfig
    from repro.models.dlrm import DLRMConfig

    def extra(name):
        return {k: _frozen(v) for k, v in cfg.get(name, {}).items()}
    return DLRMConfig(
        dense_features=cfg["dense_features"],
        bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]),
        interaction=cfg["interaction"],
        dtype=cfg["dtype"],
        embedding=EmbeddingStageConfig(
            num_tables=cfg["num_tables"], rows=_frozen(cfg["rows"]),
            dim=cfg["dim"], pooling=_frozen(cfg["pooling"]),
            dtype=cfg["dtype"], combine=cfg["combine"],
            backend=cfg["backend"], storage=cfg["storage"],
            shard_pad_tables=cfg["shard_pad_tables"],
            **extra("embedding_args")),
        **extra("model_args"))


class Deployment:
    """The model, its storage built over `params`, and an open session.

    `pooled_tap`, when the storage's lookup runs outside the jitted
    engine (host-backed backends), collects each batch's pooled rows as
    the engine receives them, for the check after the window."""

    def __init__(self, cfg: dict, params: dict, trace: np.ndarray | None):
        from repro.models.dlrm import DLRM
        from repro.serving import BatcherConfig, ServingSession
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
        self.model = DLRM(model_config(cfg))
        storage = self.model.ebc.storage
        if "ps" in cfg:
            from repro.ps import PSConfig
            storage.build(params, PSConfig(**cfg["ps"]), trace=trace)
        self.pooled: list = []
        if not storage.capabilities().device_resident:
            lookup = self.model.ebc.apply

            def tapped(*args, **kwargs):
                out = lookup(*args, **kwargs)
                self.pooled.append(out)
                return out
            self.model.ebc.apply = tapped
        self.session = ServingSession(
            self.model, params,
            batcher=BatcherConfig(max_batch=cfg["batch"],
                                  max_wait_s=cfg["max_wait_ms"] / 1e3),
            sla_ms=1e9)
        self.storage = storage

    def close(self) -> None:
        self.session.close()
