"""Roofline extraction + cell-grid unit tests (no 512-device compile)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import specs as SP
from repro.roofline import analysis as RA


def test_all_cells_grid_is_complete():
    cells = SP.all_cells()
    assert len(cells) == 40  # 10 archs × 4 shapes
    skips = [c for c in cells if isinstance(c, SP.SkipCell)]
    runs = [c for c in cells if isinstance(c, SP.Cell)]
    assert len(skips) == 6  # pure full-attention archs skip long_500k
    assert all(s.shape == "long_500k" for s in skips)
    assert {s.arch for s in skips} == {
        "qwen1_5_0_5b", "internlm2_20b", "internvl2_2b",
        "moonshot_v1_16b_a3b", "arctic_480b", "whisper_medium",
    }
    # every runnable long_500k arch is sub-quadratic
    for c in runs:
        if c.shape == "long_500k":
            assert c.arch in SP.LONG_OK


def test_input_specs_shapes():
    c = SP.get_cell("qwen1.5-0.5b", "train_4k")
    specs = SP.input_specs(c)
    assert specs["tokens"].shape == (256, 4096)
    assert specs["labels"].shape == (256, 4096)

    c = SP.get_cell("internvl2-2b", "train_4k")
    specs = SP.input_specs(c)
    # patches + text = 4096 total sequence
    assert specs["patches"].shape[1] + specs["tokens"].shape[1] == 4096

    c = SP.get_cell("whisper-medium", "decode_32k")
    specs = SP.input_specs(c)
    assert specs["token"].shape == (128,)

    c = SP.get_cell("mamba2-1.3b", "long_500k")
    state = SP.decode_state_specs_abstract(c)
    assert state.ssm_h.shape[1] == 1  # batch 1
    assert state.kv_k is None  # attention-free


def test_parse_collectives_factors():
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1,2,3}}
  %ag.1 = bf16[64,128]{1,0} all-gather(bf16[8,128]{1,0} %y), replica_groups=[8,2]<=[16]
  %cp = f32[256]{0} collective-permute(f32[256]{0} %z), source_target_pairs={{0,1}}
  %done = f32[1024]{0} all-reduce-done(f32[1024]{0} %ar)
"""
    st = RA.parse_collectives(hlo)
    assert st.counts == {"all-reduce": 1, "all-gather": 1, "collective-permute": 1}
    # all-reduce: 4096 B × 2·3/4 ; all-gather: 16384 B × 1/2 ; permute 1024 B
    assert st.wire_bytes == pytest.approx(4096 * 1.5 + 16384 * 0.5 + 1024)


def test_parse_collectives_tuple_shapes():
    hlo = "%t = (f32[128]{0}, bf16[64]{0}) all-reduce(%a, %b), replica_groups={{0,1}}\n"
    st = RA.parse_collectives(hlo)
    assert st.bytes_by_op["all-reduce"] == 128 * 4 + 64 * 2


def test_descanned_totals_linear_solve():
    # per-layer b=10 flops, fixed a=5, L=24: m1=15, m2=25 → total = 5+240
    cost1 = {"flops": 15.0, "bytes accessed": 30.0}
    cost2 = {"flops": 25.0, "bytes accessed": 40.0}
    c1 = RA.CollectiveStats({}, {}, 7.0)
    c2 = RA.CollectiveStats({}, {}, 9.0)
    cost, wire = RA.descanned_totals(cost1, c1, cost2, c2, 24)
    assert cost["flops"] == pytest.approx(5 + 24 * 10)
    assert cost["bytes accessed"] == pytest.approx(20 + 24 * 10)
    assert wire == pytest.approx(5 + 24 * 2)
    # clamp: m2 < m1 (noise) degrades gracefully to m1
    cost, wire = RA.descanned_totals(cost2, c2, cost1, c1, 24)
    assert cost["flops"] == 25.0 and wire == 9.0


def test_model_flops_regimes():
    train = SP.get_cell("qwen1.5-0.5b", "train_4k")
    prefill = SP.get_cell("qwen1.5-0.5b", "prefill_32k")
    decode = SP.get_cell("qwen1.5-0.5b", "decode_32k")
    n = train.cfg.n_active_params()
    f_train = RA.model_flops_for_cell(train, n)
    f_prefill = RA.model_flops_for_cell(prefill, n)
    f_decode = RA.model_flops_for_cell(decode, n)
    assert f_train == pytest.approx(6 * n * 4096 * 256)
    assert f_prefill == pytest.approx(2 * n * 32768 * 32)
    # decode: 2·N·B plus KV-read flops — strictly more than the matmul part
    assert f_decode > 2 * n * 128
    assert f_decode < f_prefill


def test_moe_active_params_less_than_total():
    c = SP.get_cell("arctic-480b", "train_4k")
    assert c.cfg.n_active_params() < 0.2 * c.cfg.n_params()
    # arctic really is ~480B total
    assert 3.5e11 < c.cfg.n_params() < 6e11


def test_roofline_bottleneck_selection():
    r = RA.build_roofline(
        arch="x", shape="y", mesh_desc="m", chips=4,
        cost={"flops": 197e12, "bytes accessed": 1.0},
        wire_bytes=0.0, collective_counts={},
        model_flops=100.0,
    )
    assert r.bottleneck == "compute"
    assert r.compute_s == pytest.approx(1.0)


def test_kernel_peaks_keyed_by_device_kind():
    # a v5e chip is memory-bound at 819 GB/s for a byte-heavy launch
    assert RA.kernel_bound_s(0.0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    assert RA.achieved_fraction(197e12, 0.0, 2.0, "TPU v5 lite") == (
        pytest.approx(0.5))
    # an unknown device has no roofline, never another chip's peaks
    for kind in ("cpu", "TPU v4", "tpu"):
        with pytest.raises(ValueError, match="no published peaks"):
            RA.kernel_bound_s(1.0, 1.0, kind)
