"""Pallas batched static step: interpreter-mode bit-exactness pins.

The lane-tiled Pallas wrapper (``kernels.batched_step.lane_tiled_step``)
runs the SAME step closure ``sim._make_batched_static_step`` builds, so
these tests pin the whole chain — pallas_call blocking, scan-in-kernel
interaction, masked-validity no-ops — element-wise bit-exact against the
flat unbatched ``simulate`` oracle for every statically-routed design
(including nossd's dynamic-FC one-hot path), on CPU, with no
accelerator: exactly what CI runs under ``JAX_PLATFORMS=cpu``.

Also covered here: the occupancy planner profile (accelerator pooling by
lanes x padded chunks per device) must stay bit-exact on CPU with the
cpu profile untouched as the default, and the kernel-dispatch counters
must attribute every group to its backend.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ssd import bench, simulate
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.designs import static_design_names

STATIC_DESIGNS = static_design_names()
PARITY_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
                 "misroutes")


def _assert_parity(lane, solo, ctx):
    for f in PARITY_FIELDS:
        assert np.array_equal(getattr(lane, f), getattr(solo, f)), (ctx, f)
    assert lane.bus_hold_ticks == solo.bus_hold_ticks, ctx
    assert lane.link_hold_ticks == solo.link_hold_ticks, ctx


def _force_batched(monkeypatch, backend=None):
    """Every static pool -> one batched dispatch, on the given backend."""
    monkeypatch.setattr(SP, "SMALL_LANE_MAX_CHUNKS", 64)
    monkeypatch.setattr(SP, "_BATCH_MIN_LANES", 2)
    monkeypatch.setattr(SP, "_BATCH_MAX_PER_SHARD", 64)
    if backend is not None:
        monkeypatch.setattr(S, "LANE_BACKEND", backend)


def test_lane_tiled_step_generic_toy():
    """The wrapper itself, off the simulator: tiled grid, pytree I/O, and
    bool outputs survive the pallas_call round-trip bit-exactly."""
    from repro.kernels.batched_step import lane_tiled_step

    def step(sp, state, xs):
        tx, mask = xs
        s = state + tx * sp["gain"][:, None]
        out = (s.sum(axis=1), (s.max(axis=1) > 40) & mask)
        return s, out

    B, N = 8, 5
    sp = {"gain": jnp.arange(B, dtype=jnp.int32)}
    state = jnp.ones((B, N), jnp.int32)
    xs = (jnp.arange(B * N, dtype=jnp.int32).reshape(B, N) % 7,
          jnp.asarray([True, False] * (B // 2)))
    want = step(sp, state, xs)
    for bt in (None, 4, 3):  # 3 does not divide 8 -> single-tile fallback
        got = lane_tiled_step(step, b_tile=bt, interpret=True)(sp, state, xs)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.dtype == w.dtype
            assert np.array_equal(np.asarray(g), np.asarray(w)), bt


def test_lane_backend_resolution():
    assert S.resolve_lane_backend("xla") == "xla"
    if jax.default_backend() not in S._ACCEL_BACKENDS:
        # no Pallas compiler on CPU: "pallas" degrades honestly,
        # "auto" keeps the measured XLA path
        assert S.resolve_lane_backend("pallas") == "pallas-interpret"
        assert S.resolve_lane_backend("auto") == "xla"
    assert S.resolve_lane_backend("pallas-interpret") == "pallas-interpret"
    with pytest.raises(ValueError):
        S.resolve_lane_backend("cuda-graphs")
    # key -> backend attribution used by the PERF counters
    base = ("batched", (2, 2, 2, 2, 64), 1024, 2, (None,), 2)
    assert S.kernel_backend_of_key(base + ("xla",)) == "xla"
    assert S.kernel_backend_of_key(base + ("pallas",)) == "pallas-compiled"
    assert (S.kernel_backend_of_key(base + ("pallas-interpret",))
            == "pallas-interpret")
    assert S.kernel_backend_of_key(("lane",) + base[1:]) == "xla"


def test_pallas_step_every_static_design(tiny_cfg, tiny_txns, monkeypatch):
    """THE tentpole pin: one Pallas-interpret batched dispatch spanning
    all statically-routed designs == per-design flat ``simulate``, bit
    for bit, with the dispatch attributed to the pallas backend."""
    _force_batched(monkeypatch, backend="pallas-interpret")
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, STATIC_DESIGNS, seeds=5,
                             decompose=False)
    new = bench.PERF["groups"][g0:]
    assert {g["variant"] for g in new} == {"batched"}
    assert {g["kernel_backend"] for g in new} == {"pallas-interpret"}
    for lane, design in zip(sweep, STATIC_DESIGNS):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=5),
                       design)


@pytest.mark.parametrize("design", STATIC_DESIGNS)
def test_pallas_step_per_design_seed_sweep(tiny_cfg, tiny_txns, design,
                                           monkeypatch):
    """Homogeneous Pallas batches stay bit-exact per design — nossd's
    dynamic-FC one-hot selection included."""
    _force_batched(monkeypatch, backend="pallas-interpret")
    lanes = (design,) * 6
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, lanes, seeds=(3,) * 6,
                             decompose=False)
    solo = simulate(tiny_cfg, tiny_txns, design, seed=3)
    for lane in sweep:
        _assert_parity(lane, solo, design)


def test_pallas_masked_tail_is_noop(tiny_cfg, tiny_txns, monkeypatch):
    """Mixed-length lanes under the Pallas step: the shorter lane's
    masked (invalid) steps must stay bit-identical no-ops — the
    masked-arithmetic validity path survives the kernel wrapping."""
    _force_batched(monkeypatch, backend="pallas-interpret")
    short = {k: np.asarray(v)[: len(tiny_txns["arrival"]) // 3]
             for k, v in dict(tiny_txns).items()}
    runs = [
        (tiny_cfg, tiny_txns, ("baseline", "pnssd", "pssd"), (5, 5, 5),
         False),
        (tiny_cfg, short, ("nossd", "ideal"), (5, 5), False),
    ]
    res_long, res_short = SP.execute_sim_runs(runs)
    for res, txns, designs in ((res_long, tiny_txns,
                                ("baseline", "pnssd", "pssd")),
                               (res_short, short, ("nossd", "ideal"))):
        for lane, design in zip(res, designs):
            _assert_parity(lane, simulate(tiny_cfg, txns, design, seed=5),
                           design)


def test_occupancy_profile_parity(tiny_cfg, tiny_txns, monkeypatch):
    """The accelerator planner profile on CPU: every static lane routes
    through the batched runner pooled by occupancy, scouts keep the cpu
    layout, and every output stays bit-exact vs the flat oracle."""
    monkeypatch.setattr(SP, "PLANNER_PROFILE", "occupancy")
    designs = STATIC_DESIGNS + ("venice", "venice_minimal")
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs, seeds=7,
                             decompose=False)
    new = bench.PERF["groups"][g0:]
    by_scout = {g["scout"]: g["variant"] for g in new}
    assert by_scout.get(False) == "batched"  # static pool -> occupancy
    assert by_scout.get(True) != "batched"  # scouts keep the cpu layout
    for lane, design in zip(sweep, designs):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=7),
                       design)


def test_occupancy_budget_cuts_groups(tiny_cfg, tiny_txns, monkeypatch):
    """A one-chunk-per-device budget forces the occupancy planner to cut
    the pool into several dispatches; outputs must not change."""
    monkeypatch.setattr(SP, "PLANNER_PROFILE", "occupancy")
    monkeypatch.setattr(SP, "OCCUPANCY_CHUNKS", 1)
    designs = STATIC_DESIGNS * 2
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs,
                             seeds=tuple(range(len(designs))),
                             decompose=False)
    new = [g for g in bench.PERF["groups"][g0:] if g["variant"] == "batched"]
    assert len(new) > 1
    for lane, design, seed in zip(sweep, designs, range(len(designs))):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design,
                                      seed=seed), design)


def test_kernel_dispatch_counters(tiny_cfg, tiny_txns, monkeypatch):
    """PERF accounting: batched-vs-unbatched step share and per-backend
    group counts move when a Pallas batched group runs."""
    _force_batched(monkeypatch, backend="pallas-interpret")
    kb0 = bench.PERF["kernel_backends"].get("pallas-interpret", 0)
    sb0 = bench.PERF["steps_batched"]
    su0 = bench.PERF["steps_scout_unbatched"]
    S.simulate_sweep(tiny_cfg, tiny_txns, STATIC_DESIGNS + ("venice",),
                     seeds=2, decompose=False)
    assert bench.PERF["kernel_backends"]["pallas-interpret"] > kb0
    assert bench.PERF["steps_batched"] > sb0  # the static batch
    # the lone scout lane runs flat here and tallies into the SCOUT
    # split (ISSUE 10), not the static unbatched counter
    assert bench.PERF["steps_scout_unbatched"] > su0


def test_interpret_refused_on_accelerator(monkeypatch):
    """An accelerator never runs a kernel interpreted: asking for it
    through the lane backend, the env var or the argument raises, and
    "auto" resolves to the compiled kernels."""
    from repro.kernels.backend import default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert S.resolve_lane_backend("auto") == "pallas"
    assert S.resolve_lane_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        S.resolve_lane_backend("pallas-interpret")
    assert default_interpret() is False
    with pytest.raises(ValueError):
        default_interpret(True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError):
        default_interpret()
