"""The plain references against the program at tiny sizes on the CPU, in
float32: the engine's prefill and decode against ``nope_lm.py``; the
trainer's loss and gradients against ``resnet50.py``.  (The LM's training
reference goes in with the cell that trains the LM; PERF.md, Open
questions.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import spec

nope_lm = spec.load_module("reference", "nope_lm")
resnet50 = spec.load_module("reference", "resnet50")

TINY_LM = {"vocab_size": 61, "num_hidden_layers": 2, "hidden_size": 32,
           "num_attention_heads": 4, "ffn_dim": 128}


def test_param_shapes_are_the_programs():
    from mxnet_tpu import models
    sym = models.get_symbol("transformer-lm", vocab_size=61, num_layers=2,
                            d_model=32, heads=4, batch_size=2, seq_len=8,
                            loss_head=True)
    args = sym.list_arguments()
    shapes, _, _ = sym.infer_shape(data=(2, 8), softmax_label=(2, 8))
    prog = {n: tuple(s) for n, s in zip(args, shapes)
            if n not in ("data", "softmax_label")}
    assert prog == nope_lm.param_shapes(TINY_LM)


def test_prefill_logits_match_the_reference():
    from mxnet_tpu.models.transformer import transformer_lm_prefill
    params = nope_lm.init_params(3, TINY_LM, std=0.2)
    toks = np.random.default_rng(0).integers(1, 61, (2, 11))
    got, _, _ = transformer_lm_prefill(params, jnp.asarray(toks), heads=4)
    want = nope_lm.forward(params, toks, 4)
    assert got.shape == want.shape == (2, 11, 61)
    # float32 both sides; summation order alone differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_engine_chunked_prefill_and_paged_decode_match_the_reference():
    """Greedy tokens through the engine (chunks of 8, blocks of 4) are the
    reference's argmax at every position, teacher-forced on the engine's
    own tokens: the logit-space test the benchmark makes on the chip,
    here with a float32 engine and a tolerance of rounding only."""
    from mxnet_tpu.serve import Engine, EngineConfig
    params = nope_lm.init_params(5, TINY_LM, std=0.2)
    eng = Engine(params, EngineConfig(
        heads=4, block_size=4, num_blocks=64, max_batch=3, max_prompt_len=24,
        max_seq_len=48, prefill_chunk=8))
    eng.warmup()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 61, n).tolist() for n in (3, 8, 19)]
    ids = [eng.submit(p, max_new_tokens=9, seed=i) for i, p in enumerate(prompts)]
    eng.run()
    for rid, p in zip(ids, prompts):
        out = list(eng.request(rid).tokens)
        seq = np.asarray([p + out[:-1]])
        logits = np.asarray(nope_lm.forward(params, seq, 4))[0]
        rows = logits[len(p) - 1:]
        deficit = rows.max(axis=-1) - rows[np.arange(9), out]
        assert deficit.max() <= 1e-4, deficit


def _trainer(sym, shapes, label_shapes, **kw):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh
    mx.random.seed(3)
    tr = ShardedTrainer(
        sym, mesh=make_mesh({"data": 1}, jax.local_devices()[:1]),
        optimizer="sgd", optimizer_params={"learning_rate": 1.0}, **kw)
    tr.bind(data_shapes=shapes, label_shapes=label_shapes)
    return tr


def _grads_from_one_step(tr, batch):
    """sgd, lr 1, no momentum, no decay: old - new is the gradient of the
    mean loss (the trainer rescales by 1/batch)."""
    old = {k: v.asnumpy() for k, v in tr.get_params()[0].items()}
    heads = tr.step(batch)
    new = {k: v.asnumpy() for k, v in tr.get_params()[0].items()}
    return old, np.asarray(heads[0]), {k: old[k] - new[k] for k in old}


def test_resnet_trainer_loss_and_gradients_match_the_reference():
    from mxnet_tpu import initializer, models
    sym = models.get_symbol("resnet", depth=50, num_classes=10)
    tr = _trainer(sym, {"data": (8, 3, 64, 64)}, {"softmax_label": (8,)},
                  initializer=initializer.MSRAPrelu(factor_type="in", slope=0.0))
    rng = np.random.default_rng(4)
    batch = {"data": rng.random((8, 3, 64, 64), dtype=np.float32),
             "softmax_label": rng.integers(0, 10, (8,)).astype(np.float32)}
    old, head, grads = _grads_from_one_step(tr, batch)
    assert resnet50.program_loss(head, batch, {}) == pytest.approx(
        resnet50.reference_loss(old, batch, {}), rel=1e-4)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(resnet50.mean_loss))(
            {k: jnp.asarray(v) for k, v in old.items()},
            jnp.asarray(batch["data"]), jnp.asarray(batch["softmax_label"]))
    # float32 through 53 batch norms over 8 images amplifies rounding: against
    # a float64 run of the same reference, the reference's own float32
    # gradients are off by up to 2.4 % in the L2 norm of an early leaf and
    # the program's by up to 5 % (its one-pass variance is the noisier).
    # A wrong layer, stride or BatchNorm mode is off by the gradient's size.
    for k, w in want.items():
        w = np.asarray(w, np.float64).ravel()
        err = np.linalg.norm(grads[k].ravel() - w) / np.linalg.norm(w)
        assert err <= 0.1, (k, err)
