"""Distribution tests: sharding-rule logic (AbstractMesh, no devices needed)
plus end-to-end multi-device checks in a subprocess with 8 host devices
(the main pytest process must keep seeing 1 CPU device)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models.transformer import DistContext
from repro.parallel.sharding import (cache_spec_for, make_abstract_mesh,
                                     param_spec_for)


def _dist(shape=(16, 16), axes=("data", "model")):
    mesh = make_abstract_mesh(shape, axes)
    dp = tuple(a for a in ("pod", "data") if a in axes)
    fsdp = dp if len(dp) > 1 else "data"
    return DistContext(mesh=mesh, tp_axis="model", fsdp_axis=fsdp,
                       dp_axes=dp)


class TestParamRules:
    def test_attention_projections(self):
        d = _dist()
        assert param_spec_for("scan/0/attn/wq", (24, 3840, 3840), d,
                              has_scan_dim=True) == P(None, "data", "model")
        assert param_spec_for("scan/0/attn/wo", (24, 3840, 3840), d,
                              has_scan_dim=True) == P(None, "model", "data")

    def test_mqa_kv_falls_back_to_head_dim(self):
        """granite kv=1: wk is (D, 128); 128 divides 16 so TP shards it."""
        d = _dist()
        spec = param_spec_for("scan/0/attn/wk", (52, 6144, 128), d,
                              has_scan_dim=True)
        assert spec == P(None, "data", "model")

    def test_indivisible_dim_replicates(self):
        """gemma2 d_model=2304 fsdp-shards (2304/16=144) but a hypothetical
        odd dim must replicate."""
        d = _dist()
        spec = param_spec_for("scan/0/attn/wq", (26, 2305, 2048), d,
                              has_scan_dim=True)
        assert spec == P(None, None, "model")

    def test_moe_experts_ep_on_model(self):
        d = _dist()
        spec = param_spec_for("scan/0/moe/w_gate", (94, 128, 4096, 1536), d,
                              has_scan_dim=True)
        assert tuple(spec) == (None, "model", "data")   # trailing None dropped
        spec = param_spec_for("scan/0/moe/w_out", (94, 128, 1536, 4096), d,
                              has_scan_dim=True)
        assert spec == P(None, "model", None, "data")

    def test_embed_vocab_tp(self):
        d = _dist()
        assert param_spec_for("embed", (256000, 2304), d,
                              has_scan_dim=False) == P("model", "data")

    def test_multipod_fsdp_spans_pod(self):
        d = _dist((2, 16, 16), ("pod", "data", "model"))
        spec = param_spec_for("scan/0/attn/wq", (94, 4096, 8192), d,
                              has_scan_dim=True)
        assert spec == P(None, ("pod", "data"), "model")

    def test_norms_replicated(self):
        d = _dist()
        assert param_spec_for("scan/0/ln1/g", (24, 3840), d,
                              has_scan_dim=True) == P()


class TestCacheRules:
    def test_kv_cache_batch_and_sequence(self):
        d = _dist()
        # (L, B, S, KV, hd): B=128 shards over data; S shards over model
        # (the kvseq rule — EXPERIMENTS.md §Perf A2: sequence-sharded caches
        # avoid the per-layer cache all-gather that head-sharding causes)
        spec = cache_spec_for((48, 128, 32768, 8, 128), d, has_scan_dim=True)
        assert spec == P(None, ("data",), "model")

    def test_batch1_long_context_sp(self):
        d = _dist()
        # (L, B=1, S, KV, hd): batch unshardable -> S shards over data (SP);
        # with kvseq S would also take model, but data wins first -> the
        # model axis is left for heads/features if divisible
        spec = cache_spec_for((13, 1, 524288, 4, 256), d, has_scan_dim=True)
        assert spec[1] is None and spec[2] == "data"

    def test_rwkv_state(self):
        d = _dist()
        spec = cache_spec_for((24, 128, 32, 64, 64), d, has_scan_dim=True)
        assert spec[1] in ("data", ("data",))   # P normalizes 1-tuples


MULTI_DEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

    # 1) compressed cross-pod all-reduce ~= plain mean
    from repro.core.grad_compression import (make_crosspod_allreduce,
                                             init_error_feedback)
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 0.01}
    specs = {"w": P()}
    err = init_error_feedback(g, n_pod=2)
    fn = make_crosspod_allreduce(mesh, specs, group_size=64)
    avg, err2 = jax.jit(fn)(g, err)
    # with identical replicas the mean == the input (quantization error only)
    diff = float(jnp.max(jnp.abs(avg["w"] - g["w"])))
    assert diff < 5e-4, diff

    # 2) tiny model trains under the mesh with our shardings
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel import make_dist, make_param_shardings
    from repro.optim import linear_warmup_linear_decay
    from repro.optim.adam import adam_init
    from repro.runtime.steps import make_train_step

    cfg = get_config("qwen3-moe-235b").reduced()   # exercises MoE shard_map
    dist = make_dist(mesh)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    shardings = make_param_shardings(params, dist)
    params = jax.tree.map(jax.device_put, params, shardings)
    opt = adam_init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    step = jax.jit(make_train_step(
        cfg, lr_schedule=linear_warmup_linear_decay(1e-3, 10),
        microbatches=2, dist=dist), donate_argnums=(0, 1))
    losses = []
    for i in range(3):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses   # overfits one batch

    # 3) sharded MoE == single-device MoE (numerical equivalence)
    from repro.models.moe import moe_apply
    p_flat = tfm.init_params(cfg, jax.random.PRNGKey(0), stacked=False,
                             dtype=jnp.float32)
    l_sharded, _ = tfm.forward(cfg, p_flat, toks[:2], dist=dist)
    l_local, _ = tfm.forward(cfg, p_flat, toks[:2], dist=None)
    err = float(jnp.max(jnp.abs(l_sharded - l_local)))
    assert err < 2e-3, err
    print("MULTIDEV OK")
""")


@pytest.mark.slow
def test_multi_device_end_to_end(tmp_path):
    script = tmp_path / "multidev.py"
    script.write_text(MULTI_DEV_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEV OK" in proc.stdout
