"""Shared small utilities: typed dataclass configs, timing, logging, tree math."""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:  # pragma: no cover - import-time wiring
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s %(name)s %(levelname)s] %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(os.environ.get("REPRO_LOGLEVEL", "INFO"))


#: where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: is unset: a fixed, git-ignored directory in the checkout (the path is
#: part of the cache key, so it must never move between runs)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    once, before their first compile (tests never do).

    `JAX_COMPILATION_CACHE_DIR`, when set, places the cache and nothing
    here overrides it; otherwise it goes to `COMPILE_CACHE_DIR`. Every
    program is cached, the Pallas kernels too — they compile in far less
    than JAX's default one-second threshold. Returns the directory.
    """
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def asdict_shallow(cfg: Any) -> dict:
    """dataclasses.asdict without deep-copying jnp arrays."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def shard_map_compat(*, mesh, in_specs, out_specs, check_vma=True):
    """Decorator form of `jax.shard_map`."""
    return jax.shard_map(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def timeit_median(fn: Callable[[], Any], iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock seconds of fn() with block_until_ready on jax outputs."""
    def _run() -> float:
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    for _ in range(warmup):
        _run()
    return float(np.median([_run() for _ in range(iters)]))


def tree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))


def tree_param_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def tree_finite(tree: Any) -> bool:
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)]
    if not leaves:
        return True
    return bool(jnp.all(jnp.stack(leaves)))


def write_json(path: str, obj: Any) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=2, sort_keys=True, default=_json_default)
    os.replace(tmp, path)  # atomic


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _json_default(o: Any) -> Any:
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    if dataclasses.is_dataclass(o):
        return dataclasses.asdict(o)
    return str(o)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PiB"
