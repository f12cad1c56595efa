"""The traffic generator and the yardstick's byte count."""
import numpy as np
import pytest

from bench import costs, model as bmodel, traffic
from bench.tests import tiny


def _mix():
    return traffic.load_mix(tiny.ROOT / "bench" / "traffic" / "chat.json")


def test_every_seed_serves_the_same_lengths_in_the_same_order():
    mix = dict(_mix(), queue=128)
    a = traffic.generate(mix, 32000, 1)
    b = traffic.generate(mix, 32000, 2 ** 33 + 5)
    la = [(len(p), o) for p, o in a]
    assert la == [(len(p), o) for p, o in b]
    n = mix["block"]
    pairs = sorted(map(tuple, traffic.length_pairs(mix).tolist()))
    for k in range(0, 128, n):
        assert sorted(la[k:k + n]) == pairs
    assert la[:n] != la[n:2 * n]            # blocks in orders of their own
    assert not np.array_equal(a[0][0][:16], b[0][0][:16])
    again = traffic.generate(mix, 32000, 1)
    assert all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, again))


def test_lengths_follow_the_mix():
    mix = _mix()
    pairs = traffic.length_pairs(mix)
    assert pairs[:, 0].min() >= 64 and pairs[:, 0].max() <= 2048
    assert pairs[:, 1].min() >= 16 and pairs[:, 1].max() <= 384
    assert np.median(pairs[:, 0]) == pytest.approx(512, rel=0.05)
    assert np.median(pairs[:, 1]) == pytest.approx(96, rel=0.05)


def test_weight_bytes_equal_what_the_packing_leaves():
    """The yardstick's count of the packed parameters' bytes is what
    ``build_deploy`` leaves on the device (CPU, toy widths)."""
    import jax
    from bench import harness
    c = tiny.cell()
    prog = harness.build_program(c["conf"], c["params"], 3)
    got = sum(x.nbytes for x in jax.tree.leaves(prog.params))
    m = c["conf"]["model"]
    assert got == bmodel.arch(m).packed_weight_bytes(
        m, c["conf"]["precision"]["peg_groups"])


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError):
        costs.peaks_for("cpu")
    assert costs.peaks_for("TPU v5 lite")["int8_ops"] == 393e12


def test_chat_16_is_chat_with_16_clients():
    chat, chat16 = _mix(), traffic.load_mix(
        tiny.ROOT / "bench" / "traffic" / "chat-16.json")
    assert chat16["clients"] == 16
    assert {k: v for k, v in chat16.items() if k not in ("clients",
                                                         "source")} == \
        {k: v for k, v in chat.items() if k not in ("clients", "source")}


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_kv_token_bytes_match_the_program_arenas(kv_bits):
    """The decode roofline's bytes per cached token and layer are what the
    program's paged arenas hold (k and v, and on the int8 cache their
    scales and positions), at toy widths."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    m = tiny.MODEL
    cfg = bmodel.arch(m).model_config({"name": "tiny", "source": "test",
                                       "model": m})
    blocks, bs = 8, 4
    cache = tfm.init_cache(cfg, 2, 16, dtype=jnp.bfloat16, kv_bits=kv_bits,
                           paged=True, block_size=bs, num_blocks=blocks,
                           mapped=False)
    del cache["block_table"]
    arenas = sum(x.nbytes for x in jax.tree.leaves(cache))
    if kv_bits == 16:       # the bf16 kernel reads k and v, not positions
        arenas -= sum(x.nbytes for x in jax.tree.leaves(cache)
                      if x.dtype == jnp.int32)
    assert arenas == blocks * bs * sum(
        costs.kv_token_bytes(layer, kv_bits)
        for layer in bmodel.arch(m).layers(m))
