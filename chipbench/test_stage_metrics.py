"""Readers of the planner's stage timers and the put counter, on a
hand-built context; none reads anything from a program without them."""
import pytest

from chipbench import run as R

SHARES = ("lower_share", "pack_share", "put_share", "fetch_share",
          "unpack_share")


def _ctx(**perf):
    return dict(perf=dict(sim_s=8.0, exec_s=5.0, scan_steps_valid=1000,
                          **perf),
                host_s=10.0)


@pytest.mark.parametrize("name,timer,value", [
    ("lower_share", "lower_s", 0.5),
    ("pack_share", "pack_s", 1.2),
    ("put_share", "put_s", 0.8),
    ("fetch_share", "fetch_s", 0.3),
    ("unpack_share", "unpack_s", 0.4),
])
def test_share_readers(name, timer, value):
    assert R.read_metric(name, _ctx(**{timer: value})) == pytest.approx(
        10.0 * value)


def test_put_bytes_per_valid():
    assert R.read_metric("put_bytes_per_valid",
                         _ctx(put_bytes=64_000)) == pytest.approx(64.0)
    ctx = _ctx(put_bytes=64_000)
    ctx["perf"]["scan_steps_valid"] = 0
    assert R.read_metric("put_bytes_per_valid", ctx) is None


@pytest.mark.parametrize("name", SHARES + ("put_bytes_per_valid",))
def test_nothing_to_read_without_the_program_s_timers(name):
    assert R.read_metric(name, _ctx()) is None


def test_host_stages_add_up_to_host_pack_share():
    """host_pack_share (sim_s - exec_s) holds the four host stages."""
    ctx = _ctx(lower_s=0.5, pack_s=1.2, put_s=0.8, unpack_s=0.4)
    parts = sum(R.read_metric(n, ctx) for n in
                ("lower_share", "pack_share", "put_share", "unpack_share"))
    assert parts <= R.read_metric("host_pack_share", ctx)
