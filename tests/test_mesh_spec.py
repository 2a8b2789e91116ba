"""CLI mesh-spec parsing (launch/mesh.py) — pure string processing, so
these run without any device emulation."""

import pytest

from repro.launch.mesh import mesh_spec_size, parse_mesh_spec
from repro.launch.solve_maxcut import build_parser


def test_parse_basic_specs():
    assert parse_mesh_spec("data=2") == {"data": 2}
    assert parse_mesh_spec("data=2,model=4") == {"data": 2, "model": 4}
    assert parse_mesh_spec(" data = 2 , model = 4 ") == {"data": 2, "model": 4}
    assert mesh_spec_size({"pod": 2, "data": 3, "model": 4}) == 24


def test_parse_normalizes_axis_order():
    # canonical (pod, data, model) order regardless of flag spelling
    spec = parse_mesh_spec("model=4,data=2,pod=2")
    assert list(spec) == ["pod", "data", "model"]


@pytest.mark.parametrize(
    "bad",
    [
        "",
        "   ",
        "data",  # missing =
        "data=",  # missing size
        "data=x",  # non-integer
        "data=2.5",  # non-integer
        "data=0",  # non-positive
        "data=-2",
        "batch=2",  # unknown axis
        "data=2,data=4",  # duplicate axis
        "model=3",  # model must be a power of two
        "model=6",
        "data=2,,model=4",  # empty entry
    ],
)
def test_parse_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_mesh_spec(bad)


def test_parse_accepts_power_of_two_model():
    for m in (1, 2, 4, 8, 16):
        assert parse_mesh_spec(f"model={m}")["model"] == m


def test_solver_cli_exposes_mesh_flags():
    args = build_parser().parse_args(
        ["--n", "100", "--mesh", "data=2,model=4", "--schedule", "faithful",
         "--merge", "striped"]
    )
    assert args.mesh == "data=2,model=4"
    assert args.schedule == "faithful"
    assert args.merge_mode == "striped"
    # every registered flag carries help text (the --help audit)
    for action in build_parser()._actions:
        assert action.help, f"flag {action.option_strings} has no help text"


def test_striped_beam_width_covers_presplit_frontier():
    """Regression: the width must cover the full 2·K^split pre-split
    frontier (it once used 2·K^(split-1), pruning partial-score rows)."""
    from repro.core.merge import exact_beam_width, striped_beam_width

    for k, m, n, sl in [(2, 5, 8, 2), (2, 6, 4, 2), (3, 4, 2, 3), (2, 7, 4, 1)]:
        w = striped_beam_width(k, m, n, sl)
        assert w is not None
        assert w >= 2 * k ** min(sl, m - 1)
        assert w <= exact_beam_width(k, m)  # never wider than one device
    # heuristic regime: exhaustive sweep over the cap → None
    assert striped_beam_width(2, 45, 2, 1, cap=1 << 18) is None


def test_solver_cli_rejects_malformed_mesh():
    from repro.launch import solve_maxcut

    with pytest.raises(ValueError):
        solve_maxcut.run(["--n", "16", "--mesh", "data=two"])
    with pytest.raises(ValueError):
        solve_maxcut.run(["--n", "16", "--mesh", "rows=4"])


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_device_shortage_advice_only_on_cpu(monkeypatch, backend):
    """Only the CPU backend can emulate devices, so only there does the
    error advise the host-device flag."""
    import jax

    from repro.launch.mesh import too_few_devices

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    msg = too_few_devices("--mesh data=4", 4, 1)
    assert msg.startswith("--mesh data=4 needs 4 devices but only 1")
    assert ("xla_force_host_platform_device_count" in msg) == (
        backend == "cpu")
