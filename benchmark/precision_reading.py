#!/usr/bin/env python3
"""The other side of a serving configuration's limits: what the plain
reference gives when it is computed in a precision BELOW the one the
configuration states, read by the comparisons that decide ``correct``.

    python3 benchmark/precision_reading.py --config brumby-14b-6of40 --seed 7

For a model whose layers keep a recurrent state (``brumby``).  A
stand-in for the engine -- the layer's RECURRENT form written here in
plain ``jax.numpy`` (float32 activations against the stored weights, one
state per key/value head) -- is handed to ``harness.serving.probe`` in
the engine's place, so the numbers printed are the probe's own:

1. as stated (float32 state): the stand-in must pass, or it is wrong;
2. the state (``S`` and ``z``) rounded to bfloat16 after every position;
3. the weights rounded to float8 e4m3 (the configuration serves
   bfloat16).

Then ``runners/serve_engine_closed_state.py::state_error``, the cell's
second number, over a pool as the program makes it and over the same
pool rounded to bfloat16 after every step.  A reading in a lower
precision has to FAIL one of the cell's limits, and the cell's own runs
(their ``[correct]`` lines) have to pass both with room.  Needs the chip
for the published widths (``--rehearsal``: tiny, CPU)."""
from __future__ import annotations

import argparse
import functools
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def recurrent_program(cfg, ref, prompts, new, round_state):
    """The recurrent form as one jitted function of the parameters:
    ``prompts`` (lists of ids) are absorbed a position at a time, all
    rows in step, then ``new`` tokens are chosen by argmax; it returns
    the token chosen after every position, ``[positions, rows]``.
    ``round_state`` rounds ``S`` and ``z`` to bfloat16 after every
    position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, layers, _, heads, kv, hd, _ = ref.dims(cfg)
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    rows, hi = len(prompts), jax.lax.Precision.HIGHEST
    lengths = np.array([len(p) for p in prompts], np.int32)
    total = int(lengths.max()) + new - 1
    forced = np.zeros((rows, total), np.int32)
    for i, p in enumerate(prompts):
        forced[i, :len(p)] = p
    iu, ju = np.triu_indices(hd)
    coef = (np.where(iu == ju, 1.0, np.sqrt(2.0)) / np.sqrt(hd)).astype(
        np.float32)
    inv = theta ** (-np.arange(hd // 2, dtype=np.float32) * 2.0 / hd)

    def phi(a):              # phi(a) . phi(b) = (a . b)^2 / hd
        return a[..., iu] * a[..., ju] * coef

    def lin(x, w):
        """``x`` float32 [rows, in] against the stored ``w`` [out, in],
        exactly: a bfloat16 weight meets the three bfloat16 pieces of
        ``x`` in one product (no float32 copy of the weights is made)."""
        if w.dtype != jnp.bfloat16:
            return jnp.matmul(x, w.T.astype(jnp.float32), precision=hi)
        parts, rest = [], x
        for _ in range(3):
            parts.append(rest.astype(jnp.bfloat16))
            rest = rest - parts[-1].astype(jnp.float32)
        out = jax.lax.dot_general(
            jnp.concatenate(parts), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return out.reshape(3, x.shape[0], -1).sum(0)

    def rope(x, t):
        ang = t.astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def kept(s):
        # not ``astype`` there and back: XLA drops such a pair on the TPU
        return jax.lax.reduce_precision(s, 8, 7) if round_state else s

    def position(params, carry, t):
        states, prev = carry
        tok = jnp.where(t < lengths, jnp.asarray(forced)[:, t], prev)
        x = jnp.take(params["embed_weight"], tok, axis=0).astype(jnp.float32)
        out = []
        for i, (s, z) in enumerate(states):
            p = {k[len(f"layer{i}_"):]: v for k, v in params.items()
                 if k.startswith(f"layer{i}_")}
            f32 = {k: v.astype(jnp.float32) for k, v in p.items()
                   if v.ndim == 1}
            h = ref._rms(x, f32["ln1_gamma"], eps)
            q = lin(h, p["q_weight"]).reshape(rows, heads, hd)
            k = lin(h, p["k_weight"]).reshape(rows, kv, hd)
            v = lin(h, p["v_weight"]).reshape(rows, kv, hd)
            q = rope(ref._rms(q, f32["q_norm_gamma"], eps), t)
            k = rope(ref._rms(k, f32["k_norm_gamma"], eps), t)
            gam = jnp.exp(jax.nn.log_sigmoid(
                lin(h, p["gate_weight"]) + f32["gate_bias"]))
            pk = phi(k)                                      # [rows, KV, D]
            s = kept(gam[..., None, None] * s
                     + pk[..., None] * v[:, :, None, :])
            z = kept(gam[..., None] * z + pk)
            out.append((s, z))
            pq = phi(q.reshape(rows, kv, heads // kv, hd))
            num = jnp.einsum("bkgi,bkid->bkgd", pq, s, precision=hi)
            den = jnp.einsum("bkgi,bki->bkg", pq, z, precision=hi)
            y = num / (den[..., None] + ref.RETENTION_EPS)
            x = x + lin(y.reshape(rows, heads * hd), p["proj_weight"])
            h = ref._rms(x, f32["ln2_gamma"], eps)
            x = x + lin(jax.nn.silu(lin(h, p["ffn_gate_weight"]))
                        * lin(h, p["ffn_up_weight"]), p["ffn_down_weight"])
        h = ref._rms(x, params["final_ln_gamma"].astype(jnp.float32), eps)
        nxt = jnp.argmax(lin(h, params["lm_head_weight"]), -1).astype(
            jnp.int32)
        return (out, nxt), nxt

    d = hd * (hd + 1) // 2
    states = [(jnp.zeros((rows, kv, d, hd), jnp.float32),
               jnp.zeros((rows, kv, d), jnp.float32))] * layers
    return jax.jit(lambda params: jax.lax.scan(
        functools.partial(position, params),
        (states, jnp.zeros((rows,), jnp.int32)),
        jnp.arange(total, dtype=jnp.int32))[1])


def recurrent_generate(cfg, ref, params, prompts, new, round_state):
    """The ``new`` greedy tokens of each prompt."""
    import numpy as np
    picked = np.asarray(recurrent_program(cfg, ref, prompts, new,
                                          round_state)(params)).T
    return [picked[i, len(p) - 1:len(p) - 1 + new].tolist()
            for i, p in enumerate(prompts)]


class StandIn:
    """What ``serving.probe`` asks of an engine: ``submit``, ``run``,
    ``request(i).tokens`` / ``.state``."""

    def __init__(self, generate):
        self.generate, self.prompts, self.new, self.out = generate, [], 0, []

    def submit(self, prompt, max_new_tokens, seed):
        self.prompts.append(list(prompt))
        self.new = int(max_new_tokens)
        return len(self.prompts) - 1

    def run(self):
        self.out = self.generate(self.prompts, self.new)

    def request(self, i):
        return types.SimpleNamespace(tokens=self.out[i], state="finished")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.harness import serving, spec
    from benchmark.run import merged

    cfg = spec.load_config(spec.load_benchmark(), args.config)
    if args.rehearsal:
        cfg = merged(cfg, cfg.get("rehearsal", {}))
    if cfg["serve"]["engine"].get("model", {}).get(
            "attention") != "power_retention":
        print(f"{args.config}: no layer keeps a recurrent state; this "
              "script has no lower-precision stand-in for it",
              file=sys.stderr)
        return 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = spec.load_module("reference", cfg["family"])
    serve = cfg["serve"]
    dtype = jnp.dtype(serve["weights_dtype"])
    run = types.SimpleNamespace(config=cfg, seed=args.seed, compared={})
    print(f"[reading] {args.config}, seed {args.seed}, on "
          f"{jax.devices()[0].device_kind}; logit_tolerance "
          f"{serve['logit_tolerance']}, state_tolerance "
          f"{serve['state_tolerance']}", flush=True)
    verdicts = {}

    def read(name, stand_in, stored):
        print(f"[reading] {name}:", flush=True)
        verdicts[name], _ = serving.probe(run, stand_in, stored, ref)

    params = ref.init_params(args.seed, cfg, dtype)
    for name, rounded in (("as stated: float32 state", False),
                          ("state rounded to bfloat16 every position", True)):
        read(name, StandIn(
            lambda pr, new, r=rounded: recurrent_generate(
                cfg, ref, params, pr, new, r)), params)

    # the float8 weights replace the stored ones leaf by leaf (two copies
    # do not fit): the tokens are made first, the stored weights drawn
    # again, and the probe is handed the tokens
    p = serve["probe"]
    rng = np.random.default_rng([int(args.seed), 0x9B0BE])   # the probe's
    prompts = [rng.integers(1, int(cfg["vocab_size"]), int(n)).tolist()
               for n in p["prompt_tokens"]]
    for k in [k for k in params if k.endswith("_weight")]:
        v = params.pop(k)
        params[k] = v.astype(jnp.float8_e4m3fn).astype(v.dtype)
        del v
    tokens = recurrent_generate(cfg, ref, params, prompts,
                                int(p["max_new_tokens"]), False)
    del params
    def replay(pr, new):
        assert pr == prompts, "the probe drew other prompts than these"
        return tokens

    read("weights rounded to float8 e4m3", StandIn(replay),
         ref.init_params(args.seed, cfg, dtype))

    from mxnet_tpu.models.retention import state_shape
    runner = spec.load_module("runners", "serve_engine_closed_state")
    like = jax.ShapeDtypeStruct(
        (1, 1, int(cfg["num_key_value_heads"]))
        + state_shape(int(cfg["head_dim"])), jnp.dtype(serve["kv_dtype"]))
    tol = float(serve["state_tolerance"])
    for name, to in (("as stated: float32 state", None),
                     ("state rounded to bfloat16 every position",
                      jnp.bfloat16)):
        err = runner.state_error(like, cfg, args.seed, to)
        print(f"[reading] state_error, {name}: {err:.3g} (tolerance {tol})",
              flush=True)
        verdicts[name] = verdicts[name] and err <= tol

    print("[reading] correct by both limits: "
          + "; ".join(f"{k}: {v}" for k, v in verdicts.items()))
    want = [True, False, False]
    ok = list(verdicts.values()) == want
    print("the stated precision passes and each lower one fails: "
          + ("yes" if ok else "NO"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
