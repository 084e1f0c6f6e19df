"""``archs/mla_moe.py`` and the ``kimi-k2-s9.decode`` cell: the harness
finds them; the layout is the program's tree; the operation counts are the
hand-computed ones; the program (prefill, then decode through the latent
cache, through the Pallas kernel in interpret mode) agrees with the float32
reference's full forward pass; an expert layer's held shares add up to the
uncut layer; no token is dropped when every token routes to one expert;
YaRN's numbers are the published formula's; the two readers read hand-made
spans and traces; and a tiny cell served through ``InferenceServer`` is
correct while its float8 controls are not."""
import dataclasses
import io
import json
import math
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs, harness
from bench import reference as R
from bench import weights as W
from bench.tests.test_bench_stats import ctx, read
from bench.tests.util import BENCH, DATA, tiny_root

CELL = "kimi-k2-s9.decode"
SEED = 2**31 + 77


def config(name="kimi-k2-s9"):
    path = (os.path.join(BENCH, "configs", name + ".json") if name == "kimi-k2-s9"
            else os.path.join(DATA, name + ".json"))
    with open(path) as f:
        return json.load(f)


def test_cell_resolves():
    cell = harness.resolve(CELL)
    assert cell.chips == 1
    assert cell.arch is archs.load({"program": {"bench_arch": "mla_moe"}})
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"tokens_per_s", "tpot_p90_ms", "setup_s", "mla_decode_roofline",
            "expert_fill", "mfu", "occupancy"} <= names
    for name in names:
        assert callable(harness.reader(name))
    srv = cell.traffic["server"]
    assert (srv["buckets"], srv["slots_per_bucket"], srv["max_new_cap"]) == (
        [2048], 16, 1024)


@pytest.mark.parametrize("name", ["kimi-k2-s9", "tiny-mla"])
def test_layout_is_the_programs_tree(name):
    from repro.models import get_model
    from repro.models.params import abstract

    c = config(name)
    arch = archs.load(c)
    cfg = arch.program_config(c)
    want = jax.tree_util.tree_map(
        lambda s: s.shape, abstract(get_model(cfg).param_spec(cfg, 1),
                                    cfg.compute_dtype))
    got = W._nest({k: s for k, (s, _, _) in arch.layout(c).items()})
    assert got == want


def test_published_sizes_as_run():
    c = config()
    cfg = archs.load(c).program_config(c)
    assert (cfg.n_experts, cfg.n_held, cfg.top_k, cfg.expert_offset) == (
        384, 8, 8, 0)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.n_layers, cfg.vocab) == (
        18432, 2048, 9, 20480)
    n = sum(math.prod(s) for s, _, _ in archs.load(c).layout(c).values())
    assert n == 4_793_133_056  # 9.59 GB in bfloat16


def test_flops_hand_computed():
    c = config()
    arch = archs.load(c)
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
            + 64 * 128 * 7168)
    assert attn == 101_122_048
    dense = attn + 3 * 7168 * 18432
    # router, shared expert, and 8 of 384 experts' share of the top-8.
    expert = attn + 7168 * 384 + 3 * 7168 * 2048 + 3 * 7168 * 2048 * 8 * 8 // 384
    head = 7168 * 20480
    token = dense + 8 * expert + head
    assert token == 1_886_322_688
    # Decode: latent attention per key, all 9 layers: 64 heads x (512 + 64)
    # for the score and 512 for the value, 2 operations each.
    assert arch.decode_flops(c, 1000) == 2 * token + 2 * 64 * 1088 * 9 * 1000
    # Prefill: expanded attention, 64 x (128 + 64 + 128) per key.
    p = 100
    assert arch.prefill_flops(c, p) == (2 * (token - head) * p + 2 * head
                                        + 2 * 64 * 320 * 9 * p * (p + 1) // 2)


def test_yarn_values():
    from repro.models import mla

    c = config()
    cfg = archs.load(c).program_config(c)
    inv = mla.yarn_inv_freq(cfg)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:20], base[:20], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], base[20:] / 32, rtol=1e-6)
    want = 192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2
    assert mla.softmax_scale(cfg) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(192 ** -0.5 * 1.8133, rel=1e-4)
    assert mla.rope_mscale(cfg) == 1.0
    ref_inv, ref_ms, ref_scale = archs.load(c).yarn(c)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert (ref_ms, ref_scale) == (1.0, pytest.approx(want, rel=1e-12))


# ------------------------------------------------------ against the reference
def _tiny():
    from repro.models import get_model

    c = config("tiny-mla")
    arch = archs.load(c)
    cfg = arch.program_config(c)
    return c, arch, cfg, get_model(cfg)


def _reference_logits(c, arch, tokens):
    """The float32 reference's full forward pass: logits at every
    position of ``tokens`` (B, T)."""
    layer, _, embed = arch._fns(json.dumps(c, sort_keys=True))
    flat = W.top_params(c, SEED, c["torch_dtype"], arch.layout)
    with jax.default_matmul_precision("highest"):
        x = embed(flat["embed"], jnp.asarray(tokens), False)
        x = layer(x, arch._nest(flat, "dense_layer/"), False)
        stack = arch._nest(flat, "moe_layers/")
        for i in range(c["num_hidden_layers"] - 1):
            x = layer(x, jax.tree_util.tree_map(lambda a: a[i], stack), False)
        x = R.rms_norm(x, flat["final_norm"], c["rms_norm_eps"])
        return np.asarray(R.mm("btd,dv->btv", x, flat["lm_head"]))


def test_prefill_then_decode_matches_the_reference_forward():
    """Prefill a prompt, then decode 6 greedy tokens through the latent
    cache (the Pallas kernel, interpreted): every step's logits match the
    reference's full pass over the same tokens."""
    from repro.serve.step import zeros_cache

    c, arch, cfg, api = _tiny()
    params = W.make_params(c, SEED, c["torch_dtype"], arch.layout)
    rng = np.random.default_rng(0)
    b, s, steps = 2, 11, 6
    tokens = rng.integers(0, c["vocab_size"], (b, s)).astype(np.int32)
    cache = zeros_cache(cfg, api, b, 32)
    logits, cache = api.prefill(params, {"tokens": jnp.asarray(tokens)}, cfg,
                                cache)
    got = [np.asarray(logits[:, -1])]
    seq = tokens
    for i in range(steps):
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)[:, None]
        seq = np.concatenate([seq, nxt], 1)
        logits, cache = api.decode(params, jnp.asarray(nxt),
                                   jnp.full((b,), s + i, jnp.int32), cfg, cache)
        got.append(np.asarray(logits[:, -1]))
    want = _reference_logits(c, arch, seq)[:, s - 1:]
    # float32 on both sides: the program's default-precision products and
    # other summation orders against HIGHEST, through 3 layers.
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=2e-4, atol=2e-4)


def _layer_params(c, arch):
    flat = W.top_params(c, SEED, c["torch_dtype"], arch.layout)
    p = arch._nest(flat, "moe_layers/")
    return jax.tree_util.tree_map(lambda a: a[0], p)


def _program_moe(cfg, p, x, held=None, offset=0):
    from repro.models import moe

    held = held or cfg.n_experts
    cfg = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    q = dict(p, experts={k: v[offset:offset + held]
                         for k, v in p["experts"].items()})
    with jax.default_matmul_precision("highest"):
        return moe.held_moe(x, q, cfg)


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips holding 4 of 16 experts each: their parts, the shared
    expert counted once, are the layer that holds all 16, and that layer is
    the reference's."""
    c, arch, cfg, _ = _tiny()
    c = dict(c, n_routed_experts=16)  # the uncut layer's weights
    p = _layer_params(c, arch)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, c["hidden_size"]))
    from repro.models import layers as L

    uncut, n_uncut = _program_moe(cfg, p, x)
    with jax.default_matmul_precision("highest"):
        shared = L.swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"],
                          p["shared"]["w_down"])
    parts = [_program_moe(cfg, p, x, 4, o) for o in (0, 4, 8, 12)]
    total = sum(y - shared for y, _ in parts) + shared
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    assert int(sum(n.sum() for _, n in parts)) == int(n_uncut.sum()) == 40 * 4
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        want = (arch.experts(x, f32, c, 0)
                + arch._swiglu(x, f32["shared"], R.ident))
    np.testing.assert_allclose(uncut, want, rtol=1e-5, atol=1e-5)


def test_no_token_dropped_when_all_route_to_one_expert():
    """A bias that sends every token to held expert 5 (of 4..7): the
    program's layer is the reference's, every token's row computed."""
    c, arch, cfg, _ = _tiny()
    p = _layer_params(dict(c, n_routed_experts=16), arch)
    p = dict(p, router_bias=p["router_bias"].at[5].add(100.0))
    x = jax.random.normal(jax.random.PRNGKey(6), (64, c["hidden_size"]))
    y, held = _program_moe(cfg, p, x, held=4, offset=4)
    assert int(jnp.min(held)) >= 1  # every token reaches expert 5
    q = dict(p, experts={k: v[4:8] for k, v in p["experts"].items()})
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), q)
        want = (arch.experts(x, f32, c, 4)
                + arch._swiglu(x, f32["shared"], R.ident))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------- readers
def X(seq, t0, t1, name, **args):
    return (seq, t0, t1, "X", name, "batcher", None, args or None)


def test_expert_fill_reads_the_segment_counters():
    spans = [X(0, 101.0, 101.1, "segment", expert_routed=3, expert_rows=128),
             X(1, 102.0, 102.1, "segment", expert_routed=5, expert_rows=128),
             X(2, 99.0, 99.1, "segment", expert_routed=100, expert_rows=128)]
    assert read("expert_fill", ctx([], spans=spans)) == pytest.approx(
        100 * 8 / 256)
    assert read("expert_fill", ctx([], spans=[X(0, 101.0, 101.1,
                                                "segment")])) is None


def test_mla_decode_roofline_reads_bytes_over_kernel_time():
    spans = [X(0, 101.0, 101.1, "segment", latent_bytes=10**9),
             X(1, 105.0, 105.1, "segment", latent_bytes=10**9)]
    device = {"window_s": 4.0, "device_ops": [
        ["%mla_decode.3", 0.002], ["%while.1", 1.0], ["%mla_decode.7", 0.001]]}
    c = ctx([], spans=spans, device=device, device_kind="TPU v5 lite")
    # 3 ms in 4 profiled seconds stands for 7.5 ms in the 10 s window.
    assert read("mla_decode_roofline", c) == pytest.approx(
        100 * 2e9 / 819e9 / 0.0075)
    none = dict(device, device_ops=[["%while.1", 1.0]])
    assert read("mla_decode_roofline", ctx([], spans=spans, device=none,
                                           device_kind="TPU v5 lite")) is None
    assert read("mla_decode_roofline", ctx([], spans=spans)) is None


# -------------------------------------------------- served through the server
def _root(tmp):
    root = tiny_root(tmp)
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(DATA, "tiny-mla.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(DATA, "tiny-mla.decode.json"),
                os.path.join(bench, "traffic"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-mla", "source": "tests",
                            "file": "bench/configs/tiny-mla.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "tiny-mla.decode", "config": "tiny-mla",
                              "traffic": "tiny-mla.decode", "chips": 1,
                              "why": "tests"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.mark.parametrize("control", ["", "reference_fp8", "program_fp8_cache"],
                         ids=["sound", "reference_fp8", "program_fp8_cache"])
def test_served_cell_is_checked_against_the_reference(control, tmp_path):
    cell = harness.resolve("tiny-mla.decode", root=_root(str(tmp_path)))
    line = harness.run(cell, SEED, 1.0, not control, jax.devices()[0],
                       time.monotonic(), checks_out=io.StringIO(),
                       control=control)
    assert line["failed"] == 0
    assert line["correct"] == (not control), line["checks"]
    if not control:  # traced: the expert counters reach the reader
        fill = line["metrics"]["expert_fill"]["value"]
        assert 0 < fill <= 100
