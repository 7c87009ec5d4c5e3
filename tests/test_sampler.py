"""The sampler without a sort (ISSUE 36).

``_sample`` takes an all-greedy step's tokens by ``argmax`` alone and
finds a sampled row's k-th logit by selection.  Both are exact: the
formula the engine had until then, ``flip(sort(scaled))[topk - 1]`` for
every row, is kept HERE as the reference, and every token and every
k-th value has to equal its own, integer for integer and bit for bit.
The second half walks the jaxprs of every cache kind's decode and chunk
programs: no ``sort`` under their ``sample`` scope, and in the
all-greedy branch no PRNG either.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.analysis.program import iter_eqns
from mxnet_tpu.serve import Engine, EngineConfig
from mxnet_tpu.serve.engine import (_NEG, _kth_largest, _sample,
                                    _sample_batch, _sampler_branch,
                                    _spec_accept_row)

from test_no_float64 import (_ENGINE, _LATENT, _MAKERS, _RETENTION, H,
                             _latent_params, _lm_params, _retention_params)

V = 517                     # not a multiple of a lane, as 50,272 is not
K = 128                     # ISSUE 36's constant of the way not kept
TOPKS = (0, 1, 5, 40, K, K + 1, V)
TEMPS = (0.0, 0.8, 1.3)


def _reference_row(logits, key, temp, topk, pos):
    """``_sample_row`` as it was before ISSUE 36: a whole sort a row."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temp, 1e-6)
    vocab = logits.shape[-1]
    kth = jnp.flip(jnp.sort(scaled), -1)[jnp.clip(topk - 1, 0, vocab - 1)]
    masked = jnp.where((topk > 0) & (scaled < kth), _NEG, scaled)
    sampled = jax.random.categorical(
        jax.random.fold_in(key, pos), masked).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


_reference_batch = jax.jit(jax.vmap(_reference_row))
_new = jax.jit(_sample)


def _logits(seed, rows, topk, dtype=jnp.bfloat16):
    """Seeded logits with ties planted AT the k-th value: the row's
    k-th largest is copied over three smaller entries, so that a
    selection which kept k entries and not the ties would show."""
    rng = np.random.RandomState(seed)
    x = (rng.randn(rows, V) * 1.4).astype(np.float32)
    k = min(max(topk, 1), V)
    for r in range(rows):
        order = np.argsort(-x[r], kind="stable")
        x[r, order[min(k + 2, V - 1):][:3]] = x[r, order[k - 1]]
    return jnp.asarray(x).astype(dtype)


def _operands(seed, rows, temps, topks):
    rng = np.random.RandomState(seed + 1)
    keys = jnp.asarray(rng.randint(0, 2 ** 31, (rows, 2)).astype(np.uint32))
    pos = jnp.asarray(rng.randint(1, 4000, (rows,)).astype(np.int32))
    return (keys, jnp.asarray(np.asarray(temps, np.float32)),
            jnp.asarray(np.asarray(topks, np.int32)), pos)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.mark.parametrize("topk", TOPKS)
def test_kth_value_is_the_sorts_bit_for_bit(topk):
    x = _logits(topk, 6, topk, jnp.float32) / np.float32(0.8)
    ks = jnp.full((6,), topk, jnp.int32)
    want = jnp.take_along_axis(jnp.flip(jnp.sort(x, axis=-1), -1),
                               jnp.clip(ks - 1, 0, V - 1)[:, None], axis=-1)
    got = jax.jit(_kth_largest)(x, ks)
    np.testing.assert_array_equal(_bits(got), _bits(want[:, 0]))
    # one ``k`` for every row, as the verify window asks
    np.testing.assert_array_equal(
        _bits(jax.jit(_kth_largest)(x, jnp.int32(topk))), _bits(got))


def test_kth_value_over_signs_zeros_and_infinities():
    """Every sign and exponent the integer image has to order, each
    ``k`` of the row; a sort calls the two zeros equal, so they are
    compared as values (what ``scaled < kth`` does)."""
    row = np.array([3.5, -0.0, 0.0, -1e30, np.inf, -np.inf, 1e-45, -1e-45,
                    -2.25, 7.0, 7.0, -2.25, 1e38, -1e38], np.float32)
    x = jnp.asarray(np.tile(row, (len(row), 1)))
    ks = jnp.arange(1, len(row) + 1, dtype=jnp.int32)
    want = np.sort(row)[::-1]
    got = np.asarray(_kth_largest(x, ks))
    np.testing.assert_array_equal(got, want)
    nonzero = want != 0
    np.testing.assert_array_equal(_bits(got)[nonzero], _bits(want)[nonzero])


@pytest.mark.parametrize("temp", TEMPS)
@pytest.mark.parametrize("topk", TOPKS)
def test_batch_of_one_setting_equals_the_sorting_sampler(topk, temp):
    """All rows greedy (``temp`` 0: the ``argmax`` branch) or all
    sampled: the tokens the sort gave."""
    rows = 8
    logits = _logits(31 * topk + int(10 * temp), rows, topk)
    ops = _operands(topk, rows, [temp] * rows, [topk] * rows)
    np.testing.assert_array_equal(np.asarray(_new(logits, *ops)),
                                  np.asarray(_reference_batch(logits, *ops)))


@pytest.mark.parametrize("seed", range(4))
def test_mixed_batch_with_padding_rows_equals_the_sorting_sampler(seed):
    """A decode bucket as the engine fills it: greedy and sampled rows
    of differing ``topk`` side by side, then padding rows (all zeros:
    ``temp`` 0, ``topk`` 0, key 0)."""
    rng = np.random.RandomState(seed)
    live, rows = 11, 16
    temps = np.zeros(rows, np.float32)
    topks = np.zeros(rows, np.int32)
    temps[:live] = rng.choice(TEMPS, live)
    topks[:live] = rng.choice(TOPKS, live)
    temps[seed % live] = 0.8                 # at least one sampled row
    logits = _logits(seed, rows, 40)
    keys, _, _, pos = _operands(seed, rows, temps, topks)
    keys = keys.at[live:].set(0)
    ops = (keys, jnp.asarray(temps), jnp.asarray(topks), pos)
    np.testing.assert_array_equal(np.asarray(_new(logits, *ops)),
                                  np.asarray(_reference_batch(logits, *ops)))


@pytest.mark.parametrize("topk", (0, 40))
def test_greedy_rows_read_the_same_in_both_branches(topk):
    """The branch taken can never change a stream: ``temp == 0`` rows
    of the sampled branch (``_sample_batch``, entered because another
    row samples) carry the tokens of the all-greedy branch."""
    rows = 8
    logits = _logits(5, rows, topk)
    keys, zeros, topks, pos = _operands(5, rows, [0.0] * rows, [topk] * rows)
    greedy = np.asarray(_new(logits, keys, zeros, topks, pos))
    np.testing.assert_array_equal(
        greedy, np.asarray(jnp.argmax(logits.astype(jnp.float32), -1)))
    np.testing.assert_array_equal(
        greedy, np.asarray(_sample_batch(logits, keys, zeros, topks, pos)))
    mixed = zeros.at[3].set(1.3)
    got = np.asarray(_new(logits, keys, mixed, topks, pos))
    np.testing.assert_array_equal(np.delete(got, 3), np.delete(greedy, 3))


@pytest.mark.parametrize("temp,topk", [(0.0, 0), (0.0, 40), (0.8, 0),
                                       (0.8, 40), (1.3, K + 1)])
def test_single_row_call_equals_its_row_of_a_batch(temp, topk):
    """The prefill and chunk programs sample ONE row (scalars beside
    it): the token that row has inside a batch."""
    logits = _logits(9, 4, topk)
    ops = _operands(9, 4, [temp] * 4, [topk] * 4)
    batch = np.asarray(_reference_batch(logits, *ops))
    for r in range(4):
        one = _new(logits[r], *(o[r] for o in ops))
        assert one.shape == () and int(one) == int(batch[r])


@pytest.mark.parametrize("temp,topk", [(0.8, 40), (1.3, 5), (0.8, 0)])
def test_verify_window_shares_the_selection(temp, topk):
    """``_spec_accept_row`` masks its window by the same helper: with
    no draft in play (``live`` 0) its one token is the plain sampler's
    at that position, i.e. the sorting sampler's."""
    c = 4
    logits = _logits(13, c, topk)
    key = jnp.asarray([7, 9], jnp.uint32)
    toks = jnp.asarray([3, 5, 8, 13], jnp.int32)
    out, n = _spec_accept_row(logits, toks, jnp.int32(0), key,
                              jnp.float32(temp), jnp.int32(topk),
                              jnp.int32(21))
    want = _reference_row(logits[0], key, jnp.float32(temp), jnp.int32(topk),
                          jnp.int32(22))
    assert int(n) == 1 and int(out[0]) == int(want)


# -- the programs' text ----------------------------------------------------

def _engine(kind):
    if kind == "paged_kv":
        return Engine(_lm_params(), EngineConfig(
            **dict(_ENGINE, attn_impl="dense", prefill_chunk=8)))
    model, params, blocks = {
        "recurrent_state": (_RETENTION, _retention_params, 5),
        "paged_latent": (_LATENT, _latent_params, 24)}[kind]
    return Engine(params(), EngineConfig(
        heads=H, model=model, block_size=4, num_blocks=blocks, max_batch=4,
        max_prompt_len=16, max_seq_len=48, prefill_chunk=8,
        attn_impl="dense"))


def _prims(closed):
    return {eqn.primitive.name for eqn, _ in iter_eqns(closed)}


def _is_prng(name):
    return name.startswith(("random_", "threefry"))


_PROGRAMS = [(cache, program)
             for cache in ("paged_kv", "recurrent_state", "paged_latent")
             for program in ("decode", "prefill_chunk")]
_PROGRAMS.append(("paged_kv", "prefill"))


@pytest.mark.parametrize("cache,program", _PROGRAMS,
                         ids=["-".join(c) for c in _PROGRAMS])
def test_program_samples_without_a_sort(cache, program):
    eng = _engine(cache)
    assert eng.cache.kind == cache
    bucket = 4 if program == "decode" else 8
    closed = jax.make_jaxpr(getattr(eng, _MAKERS[program])(bucket))(
        *eng._avals(program, bucket))
    sample = [eqn for eqn, _ in iter_eqns(closed)
              if "sample" in str(eqn.source_info.name_stack)]
    assert not [e for e in sample if e.primitive.name == "sort"]
    if cache != "paged_latent":     # whose router ranks experts (top_k)
        assert "sort" not in _prims(closed)
    # every PRNG equation of the program lies inside the sampler's cond
    top = {eqn.primitive.name for eqn in closed.jaxpr.eqns}
    assert not [p for p in top if _is_prng(p)]
    conds = [e for e in sample if e.primitive.name == "cond"]
    assert len(conds) == 1, "one sampler, one choice of branch"
    greedy, sampled = (_prims(b) for b in conds[0].params["branches"])
    assert "argmax" in greedy
    assert not [p for p in greedy if _is_prng(p) or p in ("sort", "div",
                                                          "scan", "while")]
    assert any(_is_prng(p) for p in sampled) and "sort" not in sampled


def test_verify_program_takes_its_kth_value_without_a_sort():
    eng = Engine(_lm_params(), EngineConfig(
        **dict(_ENGINE, attn_impl="dense", speculate=True, spec_k=3)))
    closed = jax.make_jaxpr(eng._make_verify_fn(4))(*eng._avals("verify", 4))
    assert "sort" not in _prims(closed)


def test_host_names_the_branch_by_the_programs_predicate():
    temps = np.zeros(8, np.float32)
    assert _sampler_branch(temps) == "greedy"
    temps[5] = 0.8
    assert _sampler_branch(temps) == "select"
