"""End-to-end behaviour: tiny DLRM train run (loss decreases), the
optimizers, serve loop over the paper's hotness datasets."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.embedding import EmbeddingStageConfig
from repro.data import DLRMQueryStream
from repro.models.dlrm import DLRM, DLRMConfig
from repro.optim import rowwise_adagrad_init, rowwise_adagrad_update


def _small_dlrm():
    return DLRMConfig(embedding=EmbeddingStageConfig(
        num_tables=4, rows=512, dim=128, pooling=8))


def test_dlrm_training_loss_decreases():
    cfg = _small_dlrm()
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    stream = DLRMQueryStream(num_tables=4, rows=512, pooling=8,
                             batch_size=32, hotness="med_hot", seed=0)

    @jax.jit
    def step(params, dense, idx, labels):
        loss, grads = jax.value_and_grad(model.loss)(params, dense, idx,
                                                     labels)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return params, loss

    losses = []
    for _ in range(30):
        b = stream.next_batch()
        # learnable signal: label = f(first table's pooled sum)
        params, loss = step(params, jnp.asarray(b.dense),
                            jnp.asarray(b.indices), jnp.asarray(b.labels))
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_rowwise_adagrad_on_embedding_tables():
    tables = {"t": jnp.ones((8, 16, 4))}
    grads = {"t": jnp.ones((8, 16, 4))}
    st = rowwise_adagrad_init(tables)
    assert st["acc"]["t"].shape == (8, 16)
    new, st = rowwise_adagrad_update(tables, grads, st, lr=0.1)
    assert float(jnp.abs(new["t"] - tables["t"]).max()) > 0
    # second step shrinks (adagrad decay)
    new2, _ = rowwise_adagrad_update(new, grads, st, lr=0.1)
    d1 = float(jnp.abs(new["t"] - tables["t"]).mean())
    d2 = float(jnp.abs(new2["t"] - new["t"]).mean())
    assert d2 < d1


def test_gradient_compression_error_feedback():
    import jax.numpy as jnp
    from repro.optim import apply_error_feedback, compress_grads
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                          jnp.float32)}
    comp, resid = compress_grads(g)
    assert comp["w"].dtype == jnp.bfloat16
    # error feedback recovers what compression lost
    recovered = apply_error_feedback(
        {"w": comp["w"].astype(jnp.float32)}, resid)
    np.testing.assert_allclose(np.asarray(recovered["w"]),
                               np.asarray(g["w"]), rtol=1e-6, atol=1e-6)


def test_serve_paper_pipeline_hotness_ordering():
    """End-to-end serve across hotness datasets using the XLA backend; the
    embedding-only fraction exists and every hotness level runs."""
    from repro.serving import BatcherConfig, InferenceServer, Query
    cfg = _small_dlrm()
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fwd = jax.jit(lambda d, i: model.forward(params, d, i))

    for hotness in ("one_item", "high_hot", "random"):
        stream = DLRMQueryStream(num_tables=4, rows=512, pooling=8,
                                 batch_size=8, hotness=hotness, seed=1)
        srv = InferenceServer(fwd, BatcherConfig(max_batch=8, max_wait_s=0.0),
                              sla_ms=10_000)
        b = stream.next_batch()
        for q in range(8):
            srv.submit(Query(qid=q, dense=b.dense[q], indices=b.indices[q]))
        srv.drain()
        assert srv.stats.served == 8
