"""The harness: BENCHMARK.json and the files it names agree; no chip, no
result; a run at a small size is correct, and is not once its engine is
broken or the control stands in for it."""
import json
import os
import shutil
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.references import dlrm

ROOT = run.ROOT


def bench_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_file_and_unit():
    spec = bench_spec()
    configs = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json"))
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert run.load_reader(m["name"]).UNIT == m["unit"]
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        moved = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


def run_script(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         bench_spec()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = run_script(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def tiny_config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "dlrm-prod-device32.json")) as f:
        cfg = json.load(f)
    cfg.update(num_tables=3, rows=4000, dim=16, pooling=6, batch=32,
               bottom_mlp=[64, 32, 16], top_mlp=[16, 8, 1])
    return cfg


TRAFFIC = {"hotness": "med_hot", "loop": "closed", "queued_batches": 2,
           "pool_batches": 4}


def tiny_run(fault=None, seed=2**31 + 77):
    return run.run_cell(tiny_config(), TRAFFIC, seed, 0.5, False,
                        ["qps"], time.perf_counter(), fault=fault)


def break_engine(alter):
    def fault(session):
        forward = session.server.forward
        session.server.forward = lambda d, i: alter(forward(d, i))
    return fault


def test_a_sound_run_is_correct():
    res = tiny_run()
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("alter", [
    # one answer altered where it is produced
    lambda s: s.at[5].add(1.0),
    # half of the batch left out, the mean of the rest in its place
    lambda s: jnp.concatenate([s[:len(s) // 2],
                               jnp.full(len(s) - len(s) // 2,
                                        jnp.mean(s[:len(s) // 2]))]),
], ids=["answer_altered", "half_batch_left_out"])
def test_a_broken_engine_is_not_correct(alter):
    res = tiny_run(break_engine(alter))
    assert not res["correct"]
    assert res["check"]["logit_gap"]["value"] > \
        res["check"]["logit_gap"]["limit"]


def test_the_control_fails_the_limit():
    """bfloat16 arithmetic in the reference's place: its answers lie
    beyond the limit the float32 program keeps."""
    cfg = tiny_config()
    made = run.generator.make_traffic(cfg, TRAFFIC, 5, 2, cfg["batch"])
    sel = np.arange(len(made))
    ref, _ = dlrm.reference(5, cfg, made.indices, made.dense, sel)
    ctl, _ = dlrm.reference(5, cfg, made.indices, made.dense, sel,
                            dtype=jnp.bfloat16)
    assert run.gap(ctl, ref) > 3 * cfg["correct"]["logit_gap"]


def test_a_ragged_config_reaches_the_program_config():
    from bench.deploy import model_config
    cfg = dict(tiny_config(), num_tables=3, rows=[10, 1000, 3],
               pooling=[1, 5, 2], embedding_args={"batch_block": 16})
    mc = model_config(cfg)
    assert mc.embedding.rows == (10, 1000, 3)
    assert mc.embedding.pooling == (1, 5, 2)
    assert mc.embedding.batch_block == 16
    assert mc.bottom_mlp == (64, 32, 16)
    # model_args reach DLRMConfig, which names the field it lacks
    with pytest.raises(TypeError, match="cross_layers"):
        model_config(dict(cfg, model_args={"cross_layers": 3}))


def test_the_control_reads_the_reference_the_config_names(monkeypatch):
    stub = types.ModuleType("bench.references.stub")

    def reference(seed, cfg, indices, dense, select, dtype=jnp.float32,
                  want_pooled=False):
        logits = np.ones(len(select), np.float32)
        return (logits if dtype == jnp.float32 else 1.5 * logits), None
    stub.reference = reference
    monkeypatch.setitem(sys.modules, "bench.references.stub", stub)
    from bench import control
    cfg = dict(tiny_config(), reference="stub")
    assert control.readings(cfg, TRAFFIC, 3, 1) == {"logit_gap": 0.5}


def test_free_deletes_every_embedding_leaf():
    tree = {"tables": jnp.zeros(3), "offsets": [jnp.ones(2), jnp.ones(1)]}
    tree["offsets"][1].delete()
    run.free(tree)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(tree))
