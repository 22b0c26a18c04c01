"""The one compile path: group keys, ``ensure_compiled``'s two tiers, and
the background pool's in-flight futures.

Every program variant resolves its key through the in-process
``sim._EXEC_CACHE``, else a fresh compile from shape avatars.  The
planner lowers missing keys on the main thread and compiles them on
``sweep_plan._compile_pool()``; ``precompile`` and the streaming engine
hand their futures to the dispatcher through ``sweep_plan._INFLIGHT``."""
import concurrent.futures

import jax
import numpy as np
import pytest

from repro.ssd import bench
from repro.ssd import sim as S
from repro.ssd import stream
from repro.ssd import sweep_plan as SP
from repro.traces.generator import gen_trace

SIG = (2, 2, 1, 2, 64)
VARIANTS = ("lane", "lanec", "stack", "batched", "bscout")


def _key(variant, backend=None):
    if variant == "batched":
        return S.batched_group_key(SIG, 1024, 8, SP._NO_PROMO, 2, backend)
    if variant == "bscout":
        return S.bscout_group_key(SIG, 1024, 8, 2, SP._NO_PROMO, 2, backend)
    make = {"lane": S.lane_group_key, "lanec": S.lanec_group_key,
            "stack": S.stack_group_key}[variant]
    return make(SIG, 1024, 2, 1, False, SP._NO_PROMO, 2)


@pytest.mark.parametrize("variant,backend,want", [
    ("lane", None, "xla"),
    ("lanec", None, "xla"),
    ("stack", None, "xla"),
    ("batched", "xla", "xla"),
    ("batched", "pallas", "pallas-compiled"),
    ("batched", "pallas-interpret", "pallas-interpret"),
    ("bscout", "xla", "xla"),
    ("bscout", "pallas", "pallas-compiled"),
    ("bscout", "pallas-interpret", "pallas-interpret"),
])
def test_kernel_backend_of_key(variant, backend, want):
    """Each variant's key has one length whatever its backend, and the
    batched ones carry the backend last."""
    key = _key(variant, backend)
    assert len(key) == (7 if variant == "batched" else 8)
    if backend is not None:
        assert key[-1] == backend
    assert S.kernel_backend_of_key(key) == want


@pytest.fixture()
def cold(monkeypatch):
    """No executable in memory and no compile in flight: every key the
    test needs is compiled in it."""
    for fut in list(SP._INFLIGHT.values()):
        concurrent.futures.wait([fut])
    SP._INFLIGHT.clear()
    S.clear_exec_cache()
    SP._CAP_SEEN.clear()
    bench.clear_caches()
    monkeypatch.setattr(SP, "PLANNER_PROFILE", "cpu")
    yield
    SP._INFLIGHT.clear()


@pytest.fixture(scope="module")
def dispatched(tiny_cfg, tiny_txns):
    """The first dispatch of each variant on the 2x2 mesh: its key, the
    arguments and shardings the runner passed, its shard count and its
    outputs."""
    seen = {}
    run = S._run_compiled

    def spy(key, args, specs, **kw):
        outs, perf = run(key, args, specs, **kw)
        seen.setdefault(key[0], (key, args, specs, kw["n_shards"], outs))
        return outs, perf

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(S, "_run_compiled", spy)
        mp.setattr(SP, "PLANNER_PROFILE", "cpu")
        # one small lane: the flat scan
        S.simulate_sweep(tiny_cfg, tiny_txns, ("baseline",), seeds=5,
                         decompose=False)
        # more small static lanes than the batched window holds: stacks
        S.simulate_sweep(tiny_cfg, tiny_txns, ("baseline",) * 9, seeds=5,
                         decompose=False)
        # a windowed replay in one window: the state-carrying lane group
        trace = gen_trace("prxy_0", 40, seed=3, footprint_bytes=1 << 20)
        stream.stream_simulate(tiny_cfg, trace, ("baseline",), window_s=1.0)
        mp.setattr(SP, "PLANNER_PROFILE", "occupancy")
        S.simulate_sweep(tiny_cfg, tiny_txns, ("baseline", "venice"),
                         seeds=5, decompose=False)
    assert set(seen) == set(VARIANTS)
    return seen


def _leaves(tree):
    return [(np.shape(x), np.dtype(x.dtype))
            for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("variant", VARIANTS)
def test_ensure_compiled_build_then_mem(dispatched, variant):
    """The key's avatars have the structure, shapes and dtypes of what the
    runner passed; ``ensure_compiled`` builds the key once, then serves it
    from memory, and the built executable reproduces the dispatch."""
    key, args, specs, n_shards, outs = dispatched[variant]
    avatars = S._avatars_for_key(key)
    assert (jax.tree_util.tree_structure(avatars)
            == jax.tree_util.tree_structure(args))
    assert _leaves(avatars) == _leaves(args)

    S._EXEC_CACHE.pop(key, None)
    built, dt, src = S.ensure_compiled(key)
    assert src == "build" and dt > 0.0
    again, dt, src = S.ensure_compiled(key)
    assert (src, dt) == ("mem", 0.0) and again is built

    got = jax.device_get(built(*S._put_args(args, specs, n_shards)))
    if variant == "bscout":
        got = got[0]  # (StepOut, per-tile DFS counts): the runner splits
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(outs)):
        assert np.array_equal(g, w)


def test_ensure_compiled_from_main_thread_lowering(dispatched):
    """The dispatcher's split: ``lower_for_key`` lowers only a key not in
    memory, and ``ensure_compiled`` builds from that lowering."""
    key = dispatched["lane"][0]
    S.ensure_compiled(key)
    assert S.lower_for_key(key) is None
    S._EXEC_CACHE.pop(key)
    lowered = S.lower_for_key(key)
    assert lowered is not None
    compiled, _, src = S.ensure_compiled(key, lowered)
    assert src == "build" and S._EXEC_CACHE[key] is compiled


@pytest.mark.parametrize("wide_in_memory", [False, True])
def test_width_padding_reads_memory_only(tiny_cfg, tiny_txns, monkeypatch,
                                         wide_in_memory):
    """A flat pool narrower than the device count runs at its own width
    unless the full-width program is already in memory."""
    _, pools = SP._lower_runs([(tiny_cfg, tiny_txns, ("baseline",), (5,),
                                False)])
    ((sig, scout), lanes), = pools.items()
    narrow = SP._plan_pool_cpu(sig, lanes, scout)[-1].key
    wide = S.lane_group_key(sig, narrow[2], 2, *narrow[4:7], 2)
    assert narrow[3] == narrow[-1] == 1
    if wide_in_memory:
        monkeypatch.setitem(S._EXEC_CACHE, wide, object())
    else:
        monkeypatch.delitem(S._EXEC_CACHE, wide, raising=False)
    (plan,) = SP._plan_pool_cpu(sig, lanes, scout)
    assert plan.key == (wide if wide_in_memory else narrow)


def test_group_record_fields(tiny_cfg, tiny_txns):
    """What a dispatched group records: compile and execute split, no
    load time."""
    g0 = len(bench.PERF["groups"])
    S.simulate_sweep(tiny_cfg, tiny_txns, ("baseline",), seeds=5,
                     decompose=False)
    (g,) = bench.PERF["groups"][g0:]
    assert set(g) == {"variant", "lanes", "capacity", "shards", "scout",
                      "steps", "cache", "kernel_backend", "compile_s",
                      "exec_s", "put_bytes"}
    assert g["cache"] in ("mem", "build") and g["kernel_backend"] == "xla"


def _requests(tiny_cfg):
    return [SP.RunRequest("hm_0", tiny_cfg, ("baseline", "venice"), 40)]


def test_precompile_futures_adopted(cold, tiny_cfg, monkeypatch):
    """``precompile`` puts each missing key on the pool; the dispatch
    adopts those futures (nothing is lowered or built again), and a second
    pass over the same requests compiles nothing."""
    builds = []
    ensure = S.ensure_compiled

    def count(key, lowered=None):
        out = ensure(key, lowered)
        builds.append((key, out[2]))
        return out

    lowers = []
    monkeypatch.setattr(S, "ensure_compiled", count)
    monkeypatch.setattr(S, "lower_for_key",
                        lambda key: lowers.append(key))
    SP.precompile(_requests(tiny_cfg))
    futures = dict(SP._INFLIGHT)
    assert futures
    SP.execute_requests(_requests(tiny_cfg))
    assert lowers == []
    assert not set(futures) & set(SP._INFLIGHT)
    for key, fut in futures.items():
        assert S._EXEC_CACHE[key] is fut.result()[0]
    built = [k for k, src in builds if src == "build"]
    assert len(built) == len(set(built)) and set(built) == set(futures)

    c0 = bench.PERF["compile_s"]
    g0 = len(bench.PERF["groups"])
    bench.clear_caches()
    SP.precompile(_requests(tiny_cfg))
    assert not SP._INFLIGHT
    SP.execute_requests(_requests(tiny_cfg))
    assert bench.PERF["compile_s"] == c0
    assert {g["cache"] for g in bench.PERF["groups"][g0:]} == {"mem"}


def test_finished_precompile_leaves_inflight(cold, tiny_cfg):
    """A precompile that finished before the dispatch reached its key is
    served from memory, and its future leaves ``_INFLIGHT``."""
    SP.precompile(_requests(tiny_cfg))
    futures = dict(SP._INFLIGHT)
    concurrent.futures.wait(futures.values())
    g0 = len(bench.PERF["groups"])
    SP.execute_requests(_requests(tiny_cfg))
    assert not SP._INFLIGHT
    assert {g["cache"] for g in bench.PERF["groups"][g0:]} == {"mem"}


def test_stream_adopts_inflight_future(monkeypatch):
    """The streaming engine waits on an in-flight compile rather than
    starting its own."""
    key = _key("lanec")
    S._EXEC_CACHE.pop(key, None)
    fut = concurrent.futures.Future()
    fut.set_result((None, 0.0, "build"))
    monkeypatch.setitem(SP._INFLIGHT, key, fut)
    monkeypatch.setattr(S, "ensure_compiled", pytest.fail)
    assert stream._resolve_executable(key) >= 0.0
    assert key not in SP._INFLIGHT


def test_compile_wait_and_overlap_add_up(cold, tiny_cfg, tiny_txns):
    """Over one ``_execute_plans`` pass, the main thread's stall and the
    compile time hidden behind dispatch add up to the pass's compile time
    (or to the stall, where the stall was longer)."""
    names = ("compile_s", "compile_wait_s", "compile_overlap_s")
    before = {k: bench.PERF[k] for k in names}
    g0 = len(bench.PERF["groups"])
    S.simulate_sweep(tiny_cfg, tiny_txns, ("baseline", "venice"), seeds=3,
                     decompose=False)
    assert {g["cache"] for g in bench.PERF["groups"][g0:]} == {"build"}
    d = {k: bench.PERF[k] - before[k] for k in names}
    assert d["compile_s"] > 0.0
    assert d["compile_wait_s"] >= 0.0 and d["compile_overlap_s"] >= 0.0
    assert d["compile_wait_s"] + d["compile_overlap_s"] == pytest.approx(
        max(d["compile_s"], d["compile_wait_s"]))
