"""Quantized-parity witness: how far two correct implementations of one
W8A8 model disagree, and which planted faults the serve gates reject.

Builds the model exactly as ``launch/serve.py --quantize --deploy-int8
--kv-bits 8`` does (random weights from ``--seed``, PTQ, packed int8
weights; chip_smoke.py's phase (b) traffic), then measures:

* ``codes_*``: int8 codes the Pallas kernels emit vs their XLA oracles
  (kernels/ref.py) on the first layer's inputs (the embedded prompts):
  norm + quantize at attn_in (per tensor) and at ffn_in (PEG groups), and
  the PEG gate matmul's requantized hidden.
* ``flip_*``: the un-cached forward, serve's reference, against itself
  with ONE code per sequence moved by one grid step at the first quantized
  site (``embed/sum``, token 0, channel 0): over every layer and over the
  first REF_SUPERS super-blocks.
* ``served``: serve ``--verify``'s comparison, the served program's
  cached prefill + decode (the serving steps and served params) vs the
  un-cached forward.
* ``int8``/``kv``: serve's deploy gates (``serve.deploy_errors``), clean
  and with a planted fault in the ffn_in site or the kv grids:
  ``fault_peg_scales`` gives every PEG group its neighbour's scale and
  zero-point, ``fault_peg_perm`` shifts the PEG permutation by one channel
  (the packed weight rows keep theirs), ``fault_kv_grid`` writes and reads
  the k cache on the v grid. ``fault_kv_grid_served`` serves with the last
  and runs the served comparison.

Each line of output is one JSON object: the check, its relative RMS
logits error (or mismatching codes), and the tolerance serve applies.

  PYTHONPATH=src python benchmarks/quant_floor.py             # on a TPU
  PYTHONPATH=src python benchmarks/quant_floor.py --reduced   # CPU, tiny
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import Mode, QuantCtx
from repro.kernels import ref as kref
from repro.launch import serve
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.parallel import make_dist

CHIP = ["--prompt-len", "256", "--new-tokens", "32", "--batch-slots", "8",
        "--max-len", "1024", "--block-size", "16", "--prefill-chunk", "128"]
TINY = ["--prompt-len", "16", "--new-tokens", "4", "--batch-slots", "2",
        "--max-len", "64", "--block-size", "4", "--prefill-chunk", "8"]


@dataclasses.dataclass
class FlipCtx(QuantCtx):
    """QuantCtx that moves one code per sequence of the ``embed/sum`` site
    (token 0, channel 0) by one grid step, toward zero."""

    def act(self, site, x):
        out = super().act(site, x)
        if site != "embed/sum":
            return out
        step = jnp.asarray(self.act_state[site].scale,
                           jnp.float32).reshape(()).astype(out.dtype)
        v = out[:, 0, 0]
        return out.at[:, 0, 0].add(jnp.where(v > 0, -step, step))


def _codes(cfg, params, acts, toks):
    """(mismatching codes, total) per site: kernel vs XLA oracle."""
    from repro.core import deploy
    x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
    layer0 = jax.tree.map(lambda a: a[0], params["scan"][0])
    out = {}
    for site, norm in (("attn_in", "ln1"), ("ffn_in", "ln2")):
        aq = acts[f"layer/{site}"]
        g = layer0[norm]["g"]
        got = deploy.norm_quantize(cfg.norm, layer0[norm], x, aq)
        xp, gp = x, g
        if aq.perm is not None:
            xp = jnp.take(x, aq.perm, axis=-1)
            gp = jnp.take(g, aq.perm, axis=0)
        want = kref.rms_quantize_ref(xp, gp, aq.scales, aq.zps, qmin=aq.qmin,
                                     qmax=aq.qmax)
        out[site] = (got, want)
    qt = out["ffn_in"][0]
    ffn = layer0["ffn"]
    hid = acts["layer/ffn/hidden"]
    up = deploy.matmul(qt, ffn["w_up"])
    got = deploy.matmul(qt, ffn["w_gate"], activation=cfg.act, mul=up,
                        out_aq=hid).q
    want = kref.int8_matmul_peg_fused_ref(
        qt.q.reshape(-1, qt.shape[-1]), ffn["w_gate"]["q"], qt.scales,
        qt.zps, ffn["w_gate"]["s"], activation=cfg.act,
        mul=up.reshape(-1, up.shape[-1]), out_scale=hid.scales[0],
        out_zp=hid.zps[0], qmin=hid.qmin, qmax=hid.qmax)
    res = {}
    for site, (g, w) in out.items():
        res[site] = (int(jnp.sum(g.q != w)), int(w.size))
    res["ffn_hidden"] = (int(jnp.sum(got.reshape(want.shape) != want)),
                         int(want.size))
    return res


def measure(argv=None) -> dict:
    """Every measurement of the module docstring: {check: record}."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="h2o-danube3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's reduced config in f32 with tiny "
                         "traffic (CPU)")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    args = serve.build_parser().parse_args(
        ["--arch", a.arch, "--seed", str(a.seed), "--scheduler",
         "continuous", "--paged-kv", "--quantize", "--deploy-int8",
         "--kv-bits", "8"] + (["--reduced"] + TINY if a.reduced else CHIP))
    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg, dtype, dist = cfg.reduced(), jnp.float32, None
    else:
        dtype, dist = jnp.bfloat16, make_dist(make_serving_mesh(1))
    params, ctx_factory, quant = serve.build_model(args, cfg, dtype, dist)
    pol, state, acts, ref_fp = quant
    n_ref = min(serve.REF_SUPERS, cfg.n_super)
    quantized_tol = serve.TOL_F32 if dtype == jnp.float32 else None
    results = {}

    def record(check, errors, tol):
        results[check] = {"check": check, "rel_rms": errors[0],
                          "max_rel": errors[1], "tolerance": tol}
        print(json.dumps(results[check]), flush=True)

    B, T, N = args.batch_slots, args.prompt_len, args.new_tokens
    rng = np.random.RandomState(args.seed)
    prompts = rng.randint(10, cfg.vocab_size, (B, T)).astype(np.int32)
    teacher = rng.randint(10, cfg.vocab_size, (B, N - 1)).astype(np.int32)
    seqs = np.concatenate([prompts, teacher], axis=1)

    with jax.default_matmul_precision("highest"):
        codes = _codes(cfg, params, acts, prompts)
    for site, (bad, total) in codes.items():
        results[f"codes_{site}"] = {"check": f"codes_{site}",
                                    "mismatching": bad, "total": total}
        print(json.dumps(results[f"codes_{site}"]), flush=True)

    def flip_factory():
        return FlipCtx(policy=pol, mode=Mode.DEPLOY, act_state=state,
                       deploy_acts=acts)
    for name, c, p in (("flip_all_layers", cfg, params),
                       ("flip_ref_layers", cfg.with_supers(n_ref),
                        serve.first_supers(params, n_ref))):
        clean = serve.uncached(c, p, seqs, T, ctx_factory=ctx_factory,
                               dist=dist)
        flipped = serve.uncached(c, p, seqs, T, ctx_factory=flip_factory,
                                 dist=dist)
        record(f"{name}_{c.num_layers}", serve.rel_errors(clean, flipped),
               None)

    ref = serve.uncached(cfg, params, seqs, T, ctx_factory=ctx_factory,
                         dist=dist)

    def served(factory):
        _, admit, decode, chunk = serve.serving_steps(cfg, dist, factory)
        cached = serve.replay(args, cfg, params, prompts, teacher, B,
                              dtype=dtype, admit=admit, chunk_step=chunk,
                              decode=decode)
        return serve.rel_errors(ref, cached)
    tol = quantized_tol or serve.TOL_SERVED
    record("served", served(ctx_factory), tol)

    tols = {"int8": quantized_tol or serve.TOL_INT8, "kv": serve.TOL_KV8}
    peg = acts["layer/ffn_in"]
    kv = acts["layer/attn/kv"]
    perm = peg.perm if peg.perm is not None else jnp.arange(cfg.d_model)
    faults = {
        "clean": acts,
        "fault_peg_scales": {**acts, "layer/ffn_in": dataclasses.replace(
            peg, scales=jnp.roll(peg.scales, 1), zps=jnp.roll(peg.zps, 1))},
        "fault_peg_perm": {**acts, "layer/ffn_in": dataclasses.replace(
            peg, perm=jnp.roll(perm, 1))},
        "fault_kv_grid": {**acts, "layer/attn/kv": kv._replace(
            k_grid=kv.v_grid, k_zp=kv.v_zp)},
    }
    for name, fault_acts in faults.items():
        errors = serve.deploy_errors(
            args, cfg, ref_fp, params, pol, state,
            serve.deploy_ctx_factory(pol, state, fault_acts))
        for check, e in errors.items():
            record(f"{check}_{name}", e, tols[check])
    record("fault_kv_grid_served", served(serve.deploy_ctx_factory(
        pol, state, faults["fault_kv_grid"])), tol)
    return results


if __name__ == "__main__":
    measure()
