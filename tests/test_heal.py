"""graftheal (mx_rcnn_tpu/resilience/heal.py) gates — mid-run backend loss.

graftguard (tests/test_resilience.py) pinned startup acquisition and
preemption; these gates pin the failure that still killed a run dead: the
backend dying MID-STEP, hours in. Every scenario is injected
deterministically (resilience/chaos.py) on the virtual 8-device CPU mesh
and must be survived IN-PROCESS — no crash, no operator:

- device loss at step K: the run completes on its own and its final
  params are BIT-exact (f32 CPU) vs an uninterrupted run;
- double loss inside one heal window: the re-dispatch fails again and the
  second heal also succeeds (the consecutive-heal cap has headroom);
- elastic shrink: the backend comes back with 4 of 8 devices — the mesh
  is re-cut with the GLOBAL batch invariant, and the loss trajectory
  matches the uninterrupted 8-device run within the existing DP parity
  tolerances (psum reassociation only), both storage modes;
- elastic resume across topologies: an emergency save cut on 8 devices
  resumes on a 4-device mesh — the checkpoint meta sidecar converts the
  dispatch skip through the images-consumed invariant.

All tests carry the ``chaos`` marker (script/smoke_resilience.sh runs the
subset); tier-1, NOT slow.
"""

import os

import numpy as np
import pytest

from mx_rcnn_tpu.config import ResilienceConfig
from mx_rcnn_tpu.obs import open_event_log, report
from mx_rcnn_tpu.obs.events import EventLog, NullEventLog
from mx_rcnn_tpu.obs.watchdog import StallWatchdog
from mx_rcnn_tpu.parallel.partition import elastic_mesh_spec
from mx_rcnn_tpu.resilience import (
    RESUMABLE_RC,
    HealCarry,
    Healer,
    PreemptionExit,
    chaos,
)
from mx_rcnn_tpu.resilience import heal as heal_mod
from mx_rcnn_tpu.train.checkpoint import (
    checkpoint_meta,
    latest_checkpoint,
    latest_epoch,
    save_checkpoint,
)
from mx_rcnn_tpu.train.metrics import MetricBag

import _resilience_driver as driver

pytestmark = pytest.mark.chaos

#: the existing DP split-parity tolerances (tests/test_train_step.py):
#: regrouping the psum over fewer devices reassociates float sums.
LOSS_RTOL = 1e-4
PARAM_RTOL, PARAM_ATOL = 2e-3, 2e-5


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    """No injection leaks between tests (or in from the outer env)."""
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    chaos.reset()
    yield
    chaos.reset()


def _assert_trees_bitexact(a, b):
    import jax

    la = jax.tree_util.tree_leaves_with_path(a)
    lb = {jax.tree_util.keystr(p): v
          for p, v in jax.tree_util.tree_leaves_with_path(b)}
    assert len(la) == len(lb)
    for path, va in la:
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(lb[jax.tree_util.keystr(path)]),
            err_msg=jax.tree_util.keystr(path))


def _assert_trees_close(a, b):
    import jax

    la = jax.tree_util.tree_leaves_with_path(a)
    lb = {jax.tree_util.keystr(p): v
          for p, v in jax.tree_util.tree_leaves_with_path(b)}
    for path, va in la:
        np.testing.assert_allclose(
            np.asarray(va), np.asarray(lb[jax.tree_util.keystr(path)]),
            rtol=PARAM_RTOL, atol=PARAM_ATOL,
            err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# chaos spec: the new keys
# ---------------------------------------------------------------------------

def test_chaos_parse_heal_keys():
    spec = chaos.parse("device_lost_at_step=4 device_lost_count=2 "
                       "shrink_on_reacquire=4")
    assert spec.device_lost_at_step == 4 and spec.device_lost_count == 2
    assert spec.shrink_on_reacquire == 4 and spec.active


def test_chaos_device_loss_fires_armed_count_then_stops():
    spec = chaos.parse("device_lost_at_step=4 device_lost_count=2")
    spec.maybe_device_loss(3)  # below threshold: nothing
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        spec.maybe_device_loss(4)
    with pytest.raises(RuntimeError, match="2/2"):
        spec.maybe_device_loss(4)
    spec.maybe_device_loss(4)  # count spent: the backend stays up
    assert spec.maybe_shrink(list(range(8))) == list(range(8))
    assert chaos.parse("shrink_on_reacquire=4").maybe_shrink(
        list(range(8))) == [0, 1, 2, 3]


def test_chaos_die_at_site_must_be_registered():
    """A typo'd die_at site would arm an injection that can never fire —
    the same silent-un-testing hazard the unknown-key check closes."""
    with pytest.raises(ValueError, match="die_at site"):
        chaos.parse("die_at=checkpoint_finalze")
    assert chaos.parse("die_at=checkpoint_swap").die_at == "checkpoint_swap"


def test_chaos_die_at_fires_at_every_registered_site(monkeypatch):
    """parse() accepts any member of SITES for die_at, so fire() must
    route maybe_die at EVERY site — a validated-but-unroutable site
    would be exactly the armed-never-fires hole the validation closes."""
    import signal as _signal

    for site_name in sorted(chaos.SITES):
        calls = []
        monkeypatch.setattr(chaos.os, "kill",
                            lambda pid, sig: calls.append(sig))
        spec = chaos.parse(f"die_at={site_name}")
        fire = spec.fire  # aliased: the site name is a loop VARIABLE
        fire(site_name, step=10_000, devices=["d0"])
        assert calls == [_signal.SIGKILL], site_name


# ---------------------------------------------------------------------------
# elastic mesh re-derivation (parallel/partition.py)
# ---------------------------------------------------------------------------

def test_elastic_mesh_spec_shrinks_data_axis():
    # same-or-more devices: keep the footprint (growth is not a recovery)
    assert elastic_mesh_spec(8, 1, 8, 8) == "8x1"
    assert elastic_mesh_spec(8, 1, 16, 8) == "8x1"
    # the acceptance shrink: 8 -> 4, batch 8 divides
    assert elastic_mesh_spec(8, 1, 4, 8) == "4x1"
    # non-dividing counts drop to the largest batch divisor
    assert elastic_mesh_spec(8, 1, 3, 8) == "2x1"
    assert elastic_mesh_spec(8, 1, 1, 8) == "1x1"
    # model axis is preserved; data shrinks within what remains
    assert elastic_mesh_spec(4, 2, 4, 8) == "2x2"
    with pytest.raises(ValueError, match="model axis"):
        elastic_mesh_spec(4, 2, 1, 8)


# ---------------------------------------------------------------------------
# Healer unit behavior (hermetic: acquisition/teardown monkeypatched)
# ---------------------------------------------------------------------------

def _hermetic_healer(monkeypatch, tmp_path=None, devices=("d0", "d1"),
                     **rcfg_kw):
    monkeypatch.setattr(heal_mod, "_clear_backend_cache", lambda: None)
    monkeypatch.setattr(heal_mod, "acquire_backend",
                        lambda rcfg, elog=None: list(devices))
    elog = open_event_log(str(tmp_path)) if tmp_path is not None else None
    rcfg = ResilienceConfig(**rcfg_kw)
    return Healer(rcfg, elog=elog), elog


def test_healer_classifies_with_pr5_classes(monkeypatch):
    healer, _ = _hermetic_healer(monkeypatch)
    assert healer.healable(RuntimeError("UNAVAILABLE: device lost"))
    assert healer.healable(RuntimeError("ABORTED: backend restarting"))
    assert not healer.healable(RuntimeError("INVALID_ARGUMENT: shapes"))
    assert not healer.healable(ValueError("UNAVAILABLE-looking non-RT"))
    assert not healer.healable(
        RuntimeError("XlaRuntimeError: something unclassified"))
    off, _ = _hermetic_healer(monkeypatch, heal=False)
    assert not off.healable(RuntimeError("UNAVAILABLE: device lost"))


def test_healer_refuses_another_platform(monkeypatch):
    """A run that started on the chip is never healed onto whatever
    comes up once the backends were cleared: the re-acquired platform
    must be the one the run started on, anything else re-raises."""
    from types import SimpleNamespace

    cpu = [SimpleNamespace(platform="cpu")]
    tpu = [SimpleNamespace(platform="tpu")]
    healer, _ = _hermetic_healer(monkeypatch, devices=cpu)
    healer.note_devices(1, "tpu")
    lost = RuntimeError("UNAVAILABLE: device lost")
    with pytest.raises(RuntimeError, match="started on 'tpu'") as info:
        healer.recover(lost, lambda: HealCarry(params={}))
    assert info.value.__cause__ is lost and healer.heals == 0
    # the same loss heals when the chip comes back — and a later
    # session cannot re-define what the run started on
    monkeypatch.setattr(heal_mod, "acquire_backend",
                        lambda rcfg, elog=None: tpu)
    healer.note_devices(1, "cpu")
    healer.recover(lost, lambda: HealCarry(params={}))
    assert healer.heals == 1


def test_healer_live_capture_and_event(monkeypatch, tmp_path):
    healer, elog = _hermetic_healer(monkeypatch, tmp_path)
    healer.note_devices(2)
    carry = HealCarry(params={"w": np.ones(3)}, opt_state=None,
                      epoch=2, dispatch=5)
    got = healer.recover(RuntimeError("UNAVAILABLE: gone"), lambda: carry)
    elog.close()
    assert got is carry and healer.devices == ["d0", "d1"]
    assert healer.heals == 1
    (ev,) = [e for e in report.load_events(str(tmp_path))
             if e["type"] == "heal"]
    assert ev["mode"] == "live" and ev["epoch"] == 2 and ev["dispatch"] == 5
    assert ev["devices_before"] == 2 and ev["devices_after"] == 2


def test_healer_regrow_reports_against_footprint(monkeypatch, tmp_path):
    """After an 8->4 shrink, a later heal that recovers the full backend
    must report the 4->8 RE-GROW — capping at the previous (shrunken)
    session's size would log 4->4 and hide the transition."""
    healer, elog = _hermetic_healer(monkeypatch, tmp_path,
                                    devices=("a", "b", "c", "d"))
    carry = HealCarry(params={})
    healer.note_devices(8)  # nominal footprint
    healer.recover(RuntimeError("UNAVAILABLE: lost"), lambda: carry)
    healer.note_devices(4)  # the shrunken session
    healer.note_progress()
    monkeypatch.setattr(heal_mod, "acquire_backend",
                        lambda rcfg, elog=None: list("abcdefgh"))
    healer.recover(RuntimeError("UNAVAILABLE: again"), lambda: carry)
    elog.close()
    evs = [e for e in report.load_events(str(tmp_path))
           if e["type"] == "heal"]
    assert [(e["devices_before"], e["devices_after"])
            for e in evs] == [(8, 4), (4, 8)]


def test_healer_capture_failure_falls_back_to_snapshot(monkeypatch,
                                                       tmp_path):
    healer, elog = _hermetic_healer(monkeypatch, tmp_path)
    snap = HealCarry(params={"w": np.zeros(3)}, opt_state=None,
                     epoch=1, dispatch=7)
    healer.set_fallback(snap)

    def bad_capture():
        raise RuntimeError("device_get on a dead backend")

    got = healer.recover(RuntimeError("UNAVAILABLE: gone"), bad_capture)
    elog.close()
    assert got is snap
    (ev,) = [e for e in report.load_events(str(tmp_path))
             if e["type"] == "heal"]
    assert ev["mode"] == "snapshot" and ev["dispatch"] == 7


def test_healer_no_capture_no_fallback_reraises(monkeypatch):
    healer, _ = _hermetic_healer(monkeypatch)
    boom = RuntimeError("UNAVAILABLE: gone")

    def bad_capture():
        raise RuntimeError("unreadable")

    with pytest.raises(RuntimeError, match="UNAVAILABLE") as ei:
        healer.recover(boom, bad_capture)
    assert ei.value is boom


def test_healer_consecutive_cap_and_progress_rearm(monkeypatch):
    healer, _ = _hermetic_healer(monkeypatch, heal_consecutive_max=2)
    carry = HealCarry(params={})
    loss = RuntimeError("UNAVAILABLE: gone")
    for _ in range(2):
        assert healer.healable(loss)
        healer.recover(loss, lambda: carry)
    # two consecutive heals with no completed dispatch: give up...
    assert not healer.healable(loss)
    # ...unless progress happened in between — then the cap re-arms
    healer.note_progress()
    assert healer.healable(loss)


def test_healer_snapshot_cadence():
    healer = Healer(ResilienceConfig(heal_snapshot_dispatches=3))
    due = [healer.snapshot_due() for _ in range(7)]
    assert due == [False, False, True, False, False, True, False]
    assert not any(Healer(ResilienceConfig(heal_snapshot_dispatches=0))
                   .snapshot_due() for _ in range(5))


# ---------------------------------------------------------------------------
# the deferred snapshot: begun at its dispatch, installed at a later one
# ---------------------------------------------------------------------------

class _DeviceLeaf:
    """A device array's surface as DeferredSnapshot uses it. Reading one
    that is not ready is the blocking read the loop must not make; what it
    hands out is a VIEW of a buffer it keeps, as a runtime's is."""

    def __init__(self, value):
        self._buf = np.asarray(value)
        self.ready = self.transfer_started = False

    @property
    def nbytes(self):
        return self._buf.nbytes

    def copy_to_host_async(self):
        self.transfer_started = True

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        if not self.ready:
            raise AssertionError("host read of a leaf that is not ready")
        return self._buf[...]


def _pending_snapshot(healer, epoch, dispatch, bag=None, rebase=None):
    """Begin a snapshot of a two-leaf state + an optax-style count."""
    trees = ({"w": _DeviceLeaf(np.full(3, 5.0, np.float32))},
             {"count": _DeviceLeaf(np.int32(2)),
              "trace": _DeviceLeaf(np.full(3, 0.5, np.float32))})
    leaves = [trees[0]["w"], trees[1]["count"], trees[1]["trace"]]
    healer.begin_snapshot(lambda: heal_mod.DeferredSnapshot(
        trees, epoch=epoch, dispatch=dispatch, bag=bag, rebase=rebase))
    return leaves


def test_deferred_snapshot_installs_once_ready_with_taken_position(
        monkeypatch, tmp_path):
    """(i) Until every leaf is there the earlier fallback stands; the one
    installed then is tagged with the position it was TAKEN at (not the
    one it was installed at), carries the rebased schedule count and the
    bag's sums as of that position, and one `snapshot` event says so."""
    from mx_rcnn_tpu.train.optimizer import rebase_schedule_count

    healer, elog = _hermetic_healer(monkeypatch, tmp_path)
    first = HealCarry(params={"w": np.zeros(3)}, epoch=0, dispatch=0)
    healer.set_fallback(first)
    bag = MetricBag()
    bag.update({"TotalLoss": 2.0})
    bag.update({"TotalLoss": 4.0})
    for _ in range(7):
        healer.note_progress()
    leaves = _pending_snapshot(
        healer, epoch=1, dispatch=7, bag=bag.fork(),
        rebase=lambda opt: rebase_schedule_count(opt, 107))
    assert healer.snapshot_pending
    assert all(leaf.transfer_started for leaf in leaves)
    bag.update({"TotalLoss": 60.0})  # a later dispatch: not the snapshot's
    for _ in range(2):
        healer.note_progress()
        healer.poll_snapshot()  # never reads a leaf that is not ready
        assert healer._fallback is first and healer.snapshot_pending
    leaves[0].ready = leaves[1].ready = True
    healer.note_progress()
    healer.poll_snapshot()  # one leaf still out
    assert healer._fallback is first
    leaves[2].ready = True
    healer.note_progress()
    healer.poll_snapshot()
    elog.close()

    got = healer._fallback
    assert not healer.snapshot_pending and got is not first
    assert (got.epoch, got.dispatch) == (1, 7)
    np.testing.assert_array_equal(got.params["w"], np.full(3, 5.0))
    assert int(got.opt_state["count"]) == 107
    np.testing.assert_array_equal(got.opt_state["trace"], np.full(3, 0.5))
    assert got.bag == ({**{n: 0.0 for n in bag.names}, "TotalLoss": 6.0},
                       {**{n: 0 for n in bag.names}, "TotalLoss": 2})
    (ev,) = [e for e in report.load_events(str(tmp_path))
             if e["type"] == "snapshot"]
    assert (ev["epoch"], ev["dispatch"], ev["taken_at"]) == (1, 7, 7)
    assert ev["in_flight"] == 4 and ev["bytes"] == 12 + 4 + 12
    assert ev["loop_ms"] >= 0
    summary = report.summarize(report.load_events(str(tmp_path)))
    assert summary["heals"]["snapshots"] == 1
    assert summary["heals"]["snapshot_in_flight_max"] == 4
    assert "snapshots:  1 installed" in report.render(summary)


def test_one_poll_takes_a_bounded_part_of_the_loops_time(monkeypatch):
    """However large the state, a dispatch pays at most POLL_BUDGET_S (and
    one leaf) of host copies: the read spreads over the next dispatches
    and the earlier fallback stands until all of it is on the host."""
    now = [0.0]

    def clock():  # every look at the clock costs 30 ms
        now[0] += 0.03
        return now[0]

    monkeypatch.setattr(heal_mod, "_clear_backend_cache", lambda: None)
    healer = Healer(ResilienceConfig(), clock=clock)
    first = HealCarry(params={}, epoch=0, dispatch=0)
    healer.set_fallback(first)
    leaves = _pending_snapshot(healer, epoch=0, dispatch=5)
    for leaf in leaves:
        leaf.ready = True
    healer.poll_snapshot()  # 40 ms: two of the three leaves
    assert healer._fallback is first and healer.snapshot_pending
    healer.poll_snapshot()
    assert healer._fallback.dispatch == 5 and not healer.snapshot_pending


def test_second_snapshot_is_not_begun_while_one_is_pending(monkeypatch):
    healer, _ = _hermetic_healer(monkeypatch)
    leaves = _pending_snapshot(healer, epoch=0, dispatch=3)
    made = []
    healer.begin_snapshot(lambda: made.append(1))
    assert not made and healer.snapshot_pending
    for leaf in leaves:
        leaf.ready = True
    healer.poll_snapshot()
    assert healer._fallback.dispatch == 3 and not healer.snapshot_pending


def test_loss_while_snapshot_pending_falls_back_to_the_earlier_one(
        monkeypatch, tmp_path):
    """(ii) A loss with a snapshot in flight: the pending one is dropped,
    not awaited (its backend is going away), and the rollback is to the
    snapshot that was standing."""
    healer, elog = _hermetic_healer(monkeypatch, tmp_path)
    standing = HealCarry(params={"w": np.zeros(3)}, epoch=1, dispatch=7)
    healer.set_fallback(standing)
    leaves = _pending_snapshot(healer, epoch=2, dispatch=1)

    def bad_capture():
        raise RuntimeError("device_get on a dead backend")

    got = healer.recover(RuntimeError("UNAVAILABLE: gone"), bad_capture)
    assert got is standing and not healer.snapshot_pending
    for leaf in leaves:  # too late: nobody is waiting for it any more
        leaf.ready = True
    healer.poll_snapshot()
    elog.close()
    assert healer._fallback is standing
    events = report.load_events(str(tmp_path))
    (ev,) = [e for e in events if e["type"] == "heal"]
    assert ev["mode"] == "snapshot" and (ev["epoch"], ev["dispatch"]) == (1, 7)
    assert not [e for e in events if e["type"] == "snapshot"]


def test_deferred_snapshot_leaves_are_host_owned():
    """(iv) Through the real copy program: what is installed is numpy that
    owns its memory, with no jax array inside, holding the values of the
    dispatch it was taken at even after the originals are gone (the next,
    donating step takes them on the chip; here they are deleted)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # as the loop's state lies: replicated over the mesh (place_replicated)
    # or, under network.tensor_parallel, partitioned along `model`
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    rep, part = NamedSharding(mesh, P()), NamedSharding(mesh, P("model"))
    state = ({"w": jax.device_put(
                  jnp.arange(6, dtype=jnp.float32).reshape(2, 3), part),
              "b": jax.device_put(jnp.ones(3, jnp.float32), rep)},
             {"count": jax.device_put(jnp.asarray(4, jnp.int32), rep),
              "trace": {"w": jax.device_put(
                  jnp.full((2, 3), 0.25, jnp.float32), part)}})
    copy = heal_mod.compile_tree_copy(state)
    copied = copy(state)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(copied)):
        assert a.sharding == b.sharding
        assert not ({x.data.unsafe_buffer_pointer()
                     for x in a.addressable_shards}
                    & {x.data.unsafe_buffer_pointer()
                       for x in b.addressable_shards})
    snap = heal_mod.DeferredSnapshot(copied, epoch=0, dispatch=3)
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    jax.block_until_ready(copied)
    assert snap.advance()
    carry = snap.carry()
    leaves = jax.tree_util.tree_leaves((carry.params, carry.opt_state))
    assert len(leaves) == 4 and snap.nbytes == sum(x.nbytes for x in leaves)
    for leaf in leaves:
        assert type(leaf) is np.ndarray and leaf.flags.owndata
    np.testing.assert_array_equal(
        carry.params["w"], np.arange(6, dtype=np.float32).reshape(2, 3))
    assert int(carry.opt_state["count"]) == 4
    assert carry.bag is None and (carry.epoch, carry.dispatch) == (0, 3)


def test_snapshot_event_type_is_schema_legal(tmp_path):
    elog = EventLog(str(tmp_path / "e.jsonl"))
    elog.emit("snapshot", taken_at=200, in_flight=11, loop_ms=80.0,
              bytes=385_000_000)  # raises if the schema missed it
    elog.close()


# ---------------------------------------------------------------------------
# StallWatchdog.reset after a heal (satellite fix)
# ---------------------------------------------------------------------------

def test_watchdog_reset_forgets_trailing_median():
    """After a heal the first step pays re-acquisition + a fresh compile;
    judged by the pre-loss median it would read as a stall. reset() must
    re-arm with cold-start grace instead."""
    wd = StallWatchdog(NullEventLog(), stall_factor=10.0, min_stall_s=0.05,
                       poll_s=60.0)
    for _ in range(20):
        wd.beat(0.01)  # fast steady state: threshold = 10 x 0.01 = 0.1s
    assert wd.threshold_s() == pytest.approx(0.1)
    import time

    with wd._lock:  # simulate 1s without a heartbeat (the heal window)
        wd._last_beat = time.monotonic() - 1.0
    assert wd.check()  # without reset: a (false) stall fires
    with wd._lock:
        wd._last_beat = time.monotonic() - 1.0
    wd.reset()
    # post-reset: no durations -> COLD_GRACE x min_stall_s, and the
    # heal-window gap was forgotten with the beat refresh
    assert wd.threshold_s() == pytest.approx(
        StallWatchdog.COLD_GRACE * 0.05)
    assert not wd.check()
    # pause() silences the tripwire for the heal window itself (which
    # can outlast ANY threshold while acquire_backend backs off) and is
    # lifted by reset()/beat()
    wd.pause()
    with wd._lock:
        wd._last_beat = time.monotonic() - 3600.0
    assert not wd.check()
    wd.reset()
    assert not wd.check()  # reset also refreshed the beat
    wd.pause()
    wd.beat(0.01)
    with wd._lock:
        wd._last_beat = time.monotonic() - 3600.0
    assert wd.check()  # a real heartbeat lifted the pause


def test_healer_pauses_watchdog_for_the_heal_window(monkeypatch):
    """recover() must pause BEFORE capture/re-acquisition — a backend
    outage longer than the stall threshold would otherwise fire a false
    stall dump mid-heal, before the post-heal reset ran."""
    events = []

    class _WD:
        def pause(self):
            events.append("pause")

        def reset(self):
            events.append("reset")

    monkeypatch.setattr(heal_mod, "_clear_backend_cache",
                        lambda: events.append("teardown"))
    monkeypatch.setattr(heal_mod, "acquire_backend",
                        lambda rcfg, elog=None: (events.append("acquire")
                                                 or ["d0"]))
    healer = Healer(ResilienceConfig(), watchdog=_WD())
    healer.recover(RuntimeError("UNAVAILABLE: gone"),
                   lambda: HealCarry(params={}))
    assert events == ["pause", "teardown", "acquire", "reset"]


# ---------------------------------------------------------------------------
# MetricBag carry (the healed epoch keeps pre-loss accounting)
# ---------------------------------------------------------------------------

def test_metric_bag_snapshot_restore_roundtrip():
    bag = MetricBag()
    bag.update({"TotalLoss": 2.0, "RPNAcc": 0.5})
    bag.update({"TotalLoss": 4.0})
    snap = bag.snapshot()
    other = MetricBag()
    other.restore(snap)
    other.update({"TotalLoss": 6.0})
    got = other.get()
    assert got["TotalLoss"] == pytest.approx(4.0)  # (2+4+6)/3
    assert got["RPNAcc"] == pytest.approx(0.5)
    assert "RCNNAcc" not in got  # never-seen slots stay omitted


def test_rebase_schedule_count_rewrites_integer_scalars_only():
    """Elastic resume: restored optax counters are in the saving run's
    step units — rebase must rewrite exactly the scalar integer leaves
    (optax's counts) and leave slots/params untouched."""
    import optax

    from mx_rcnn_tpu.train.optimizer import rebase_schedule_count

    tx = optax.chain(optax.clip(1.0),
                     optax.sgd(optax.linear_schedule(0.1, 0.0, 100),
                               momentum=0.9))
    params = {"w": np.ones(3, np.float32)}
    opt = tx.init(params)
    # advance the counter to the "old units" position
    for _ in range(3):
        _, opt = tx.update({"w": np.ones(3, np.float32)}, opt, params)
    rebased = rebase_schedule_count(opt, 6)
    import jax

    counts = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(
        rebased) if np.asarray(leaf).ndim == 0
        and np.issubdtype(np.asarray(leaf).dtype, np.integer)]
    assert counts and all(int(c) == 6 for c in counts)
    # non-count leaves (momentum trace) survive untouched
    trace = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(
        rebased) if np.asarray(leaf).shape == (3,)]
    assert trace and not np.allclose(trace[0], 0.0)


# ---------------------------------------------------------------------------
# latest_checkpoint tie-break (satellite fix)
# ---------------------------------------------------------------------------

def test_latest_checkpoint_tie_break_emergency_wins(tmp_path, caplog):
    """"0003" (boundary) and "0003d00000" (emergency at dispatch 0) carry
    the SAME progress: the emergency save must win deterministically —
    and be loadable (the old code collapsed the tie to the boundary name
    by dict-order luck, crashing when only the emergency dir existed)."""
    (tmp_path / "0003d00000").mkdir()
    assert latest_checkpoint(str(tmp_path)) == (3, 0)  # alone: emergency
    (tmp_path / "0003").mkdir()
    import logging

    with caplog.at_level(logging.INFO):
        assert latest_checkpoint(str(tmp_path)) == (3, 0)
    assert any("tie" in r.message for r in caplog.records)
    # ordering around the tie is unchanged
    (tmp_path / "0003d00001").mkdir()
    assert latest_checkpoint(str(tmp_path)) == (3, 1)
    (tmp_path / "0004").mkdir()
    assert latest_checkpoint(str(tmp_path)) == (4, None)
    assert latest_epoch(str(tmp_path)) == 4


# ---------------------------------------------------------------------------
# checkpoint meta sidecar (the elastic axis of the tree-form contract)
# ---------------------------------------------------------------------------

def test_checkpoint_meta_roundtrip_sync_and_async(tmp_path):
    from mx_rcnn_tpu.train.checkpoint import CheckpointWriter, load_checkpoint

    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    meta = {"images_per_dispatch": 8, "device_count": 8,
            "epoch": 1, "dispatch": 2}
    prefix = str(tmp_path / "ck")
    save_checkpoint(prefix, 1, tree, dispatch=2, meta=meta)
    assert checkpoint_meta(prefix, 1, 2) == meta
    assert checkpoint_meta(prefix, 1) is None  # no such checkpoint
    # the sidecar does not disturb the array restore
    loaded, _ = load_checkpoint(prefix, 1, dispatch=2,
                                template={"w": np.zeros_like(tree["w"])})
    np.testing.assert_array_equal(loaded["w"], tree["w"])

    writer = CheckpointWriter()
    try:
        writer.save(prefix, 2, tree, meta={"images_per_dispatch": 4})
    finally:
        writer.close()  # publishes: meta lands with the rename
    assert checkpoint_meta(prefix, 2) == {"images_per_dispatch": 4}
    # pre-graftheal checkpoints (no sidecar) read as None, not an error
    save_checkpoint(prefix, 3, tree)
    assert checkpoint_meta(prefix, 3) is None


# ---------------------------------------------------------------------------
# obs.report fold
# ---------------------------------------------------------------------------

def test_report_folds_heal_events(tmp_path):
    elog = open_event_log(str(tmp_path))
    elog.emit("heal", epoch=0, dispatch=2, error="UNAVAILABLE: gone",
              mode="live", downtime_s=3.5, devices_before=8,
              devices_after=8)
    elog.emit("heal", epoch=1, dispatch=0, error="UNAVAILABLE: again",
              mode="snapshot", downtime_s=1.5, devices_before=8,
              devices_after=4)
    elog.close()
    summary = report.summarize(report.load_events(str(tmp_path)))
    assert summary["heals"]["count"] == 2
    assert summary["heals"]["downtime_s"] == pytest.approx(5.0)
    assert summary["heals"]["shrinks"] == ["8->4"]
    assert "again" in summary["heals"]["last_error"]
    assert report.bench_blob(summary)["heal_count"] == 2
    assert "heal:       2 in-run recover(ies)" in report.render(summary)
    assert "shrink 8->4" in report.render(summary)


def test_heal_event_type_is_schema_legal(tmp_path):
    elog = EventLog(str(tmp_path / "e.jsonl"))
    elog.emit("heal", downtime_s=1.0)  # raises if the schema missed it
    elog.close()


# ---------------------------------------------------------------------------
# the chaos matrix: device loss at step K, heal-and-continue parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree_baseline(tmp_path_factory):
    """The uninterrupted mesh-1 run every device-loss gate compares
    against — computed once per module (bit-deterministic, so sharing
    costs nothing and saves a full fit per test)."""
    tmp = tmp_path_factory.mktemp("heal_baseline")
    old = os.environ.pop(chaos.ENV_VAR, None)  # module scope sets up
    chaos.reset()                              # before the autouse fixture
    try:
        return driver.run_fit(str(tmp / "u"))
    finally:
        if old is not None:
            os.environ[chaos.ENV_VAR] = old


@pytest.fixture(scope="module")
def mesh8_baseline(tmp_path_factory):
    """Uninterrupted mesh-8 run: (params, per-epoch metrics)."""
    tmp = tmp_path_factory.mktemp("heal_baseline8")
    old = os.environ.pop(chaos.ENV_VAR, None)
    chaos.reset()
    try:
        metrics = []
        params = driver.run_fit(str(tmp / "u"), mesh="8", num_images=8,
                                epoch_metrics=metrics)
        return params, metrics
    finally:
        if old is not None:
            os.environ[chaos.ENV_VAR] = old


def _heal_run(tmp_path, monkeypatch, spec, expect_heals, compute="f32"):
    """Run fit under the armed chaos spec: it must complete WITHOUT
    operator intervention (no exception, no restart, no crash event),
    emitting one `heal` event per injected loss. Returns (params, heals)."""
    monkeypatch.setenv(chaos.ENV_VAR, spec)
    chaos.reset()
    obs_dir = str(tmp_path / "obs_healed")
    params_h = driver.run_fit(str(tmp_path / "healed"),
                              obs_dir=obs_dir, compute=compute)
    events = report.load_events(obs_dir)
    heals = [e for e in events if e["type"] == "heal"]
    assert len(heals) == expect_heals, heals
    assert all(e["mode"] == "live" for e in heals)
    assert [e["type"] for e in events].count("crash") == 0
    return params_h, heals


@pytest.mark.compile_heavy
def test_heal_device_loss_double_loss_parity_tree(tmp_path, monkeypatch,
                                                  tree_baseline):
    """Device loss at step K — armed to fire TWICE: the
    re-dispatch after the first heal fails again (double loss inside one
    heal window), the second heal also succeeds (the consecutive cap,
    default 3, has headroom), and the run still completes bit-exact.
    Strictly covers the single-loss case (which the shrink gate below
    also exercises on the 8-wide mesh)."""
    params_h, heals = _heal_run(
        tmp_path, monkeypatch,
        spec="device_lost_at_step=4 device_lost_count=2", expect_heals=2)
    _assert_trees_bitexact(tree_baseline, params_h)
    # loss fired before the dispatch completing step 4 (epoch 1 of 2x3,
    # dispatch 0): both captures are the last known-good position
    assert [(e["epoch"], e["dispatch"]) for e in heals] == [(1, 0), (1, 0)]


@pytest.mark.compile_heavy
def test_heal_device_loss_rolls_back_to_the_deferred_snapshot(
        tmp_path, monkeypatch, tree_baseline):
    """(iii) Through fit_detector, snapshots every 3 dispatches: the one
    begun behind dispatch 3 (the end of epoch 0) is installed during epoch
    1; the loss before the dispatch completing step 6 finds the live state
    unreadable (as donated buffers on a dead backend are), rolls back to
    that snapshot, replays epoch 0's end and dispatches 4 and 5, and the
    run still reaches the uninterrupted run's parameters bit for bit."""
    import sys

    from mx_rcnn_tpu.tools import train as train_mod

    real = train_mod.host_tree_copy

    def unreadable_from_capture(tree):
        if sys._getframe(1).f_code.co_name == "_capture":
            raise RuntimeError("donated buffer on a dead backend")
        return real(tree)

    monkeypatch.setattr(train_mod, "host_tree_copy", unreadable_from_capture)
    # two dispatches lie between the snapshot and the loss: no budget on
    # what a dispatch may copy (its own gate is above)
    monkeypatch.setattr(heal_mod.DeferredSnapshot, "POLL_BUDGET_S", 60.0)
    monkeypatch.setenv(chaos.ENV_VAR, "device_lost_at_step=6")
    chaos.reset()
    obs_dir = str(tmp_path / "obs_healed")
    params_h = driver.run_fit(
        str(tmp_path / "healed"), obs_dir=obs_dir,
        over_extra={"resilience.heal_snapshot_dispatches": 3})
    events = report.load_events(obs_dir)
    (ev,) = [e for e in events if e["type"] == "heal"]
    assert ev["mode"] == "snapshot"
    assert (ev["epoch"], ev["dispatch"]) == (0, 3)
    snaps = [e for e in events if e["type"] == "snapshot"]
    assert (snaps[0]["epoch"], snaps[0]["dispatch"],
            snaps[0]["taken_at"]) == (0, 3, 3)
    assert snaps[0]["t_mono"] < ev["t_mono"] and snaps[0]["in_flight"] >= 1
    assert [e["type"] for e in events].count("crash") == 0
    _assert_trees_bitexact(tree_baseline, params_h)


@pytest.mark.compile_heavy
def test_heal_carry_preserves_bf16_policy(tmp_path, monkeypatch,
                                          bf16_baseline):
    """graftcast across a heal: the carry is the f32 state, and the
    rebuilt session re-derives the SAME bf16 policy from cfg — so a
    healed compute_dtype=bf16 run is bit-exact vs an uninterrupted bf16
    run (the session-scope baseline shared with test_resilience's
    kill→resume gate; the module-scope f32 baseline differs by
    construction)."""
    params_h, _ = _heal_run(tmp_path, monkeypatch,
                            spec="device_lost_at_step=4", expect_heals=1,
                            compute="bf16")
    _assert_trees_bitexact(bf16_baseline, params_h)


# ---------------------------------------------------------------------------
# elastic shrink: 8 -> 4 virtual devices, global batch invariant
# ---------------------------------------------------------------------------

@pytest.mark.compile_heavy
def test_heal_shrink_8_to_4_loss_trajectory(tmp_path, monkeypatch,
                                            mesh8_baseline):
    """The backend returns with half the devices: the mesh is re-cut
    4x1, each survivor carries 2 batch rows, and the loss trajectory
    matches the uninterrupted 8-device run within the existing DP parity
    tolerances (the only difference is psum reassociation)."""
    params_u, metrics_u = mesh8_baseline

    monkeypatch.setenv(chaos.ENV_VAR,
                       "device_lost_at_step=2 shrink_on_reacquire=4")
    chaos.reset()
    metrics_h = []
    obs_dir = str(tmp_path / "obs_shrunk")
    params_h = driver.run_fit(str(tmp_path / "shrunk"), mesh="8",
                              num_images=8,
                              epoch_metrics=metrics_h, obs_dir=obs_dir)

    assert [e for e, _ in metrics_u] == [e for e, _ in metrics_h] == [0, 1]
    for (_, mu), (_, mh) in zip(metrics_u, metrics_h):
        for name, val in mu.items():
            assert np.isclose(val, mh[name], rtol=LOSS_RTOL, atol=1e-6), (
                name, val, mh[name])
    _assert_trees_close(params_u, params_h)

    (ev,) = [e for e in report.load_events(obs_dir) if e["type"] == "heal"]
    assert ev["devices_before"] == 8 and ev["devices_after"] == 4
    summary = report.summarize(report.load_events(obs_dir))
    assert summary["heals"]["shrinks"] == ["8->4"]


# ---------------------------------------------------------------------------
# elastic resume: an emergency save cut on 8 devices resumes on 4
# ---------------------------------------------------------------------------

@pytest.mark.compile_heavy
def test_elastic_resume_across_topologies(tmp_path, monkeypatch, caplog):
    """The on-disk half of the elastic contract: a dispatch-tagged save
    minted at 8 images/dispatch resumes on a 4-wide mesh — the meta
    sidecar converts 1 old dispatch into 2 new ones, so the epoch's
    trained prefix is skipped exactly (no image retrained or skipped)."""
    prefix = str(tmp_path / "run")
    monkeypatch.setenv(chaos.ENV_VAR, "sigterm_at_step=1")
    chaos.reset()
    with pytest.raises(PreemptionExit) as ei:
        driver.run_fit(prefix, mesh="8", num_images=16, end_epoch=1)
    assert ei.value.code == RESUMABLE_RC
    assert latest_checkpoint(prefix) == (0, 1)
    meta = checkpoint_meta(prefix, 0, 1)
    assert meta["images_per_dispatch"] == 8
    assert meta["device_count"] == 8 and meta["mesh"] == {"data": 8,
                                                          "model": 1}

    monkeypatch.delenv(chaos.ENV_VAR)
    chaos.reset()
    obs_dir = str(tmp_path / "obs_resumed")
    driver.run_fit(prefix, mesh="4", num_images=16, end_epoch=1,
                   resume="auto", obs_dir=obs_dir)
    # new topology: 4 images/dispatch, 4 dispatches/epoch; the 8 trained
    # images (1 old dispatch) become a 2-dispatch skip — telemetry shows
    # epoch 0 resuming at dispatch 2, never re-emitting 0/1
    resumed_e0 = sorted(e["batch"] for e in report.load_events(obs_dir)
                        if e["type"] == "step" and e.get("epoch") == 0
                        and "step_ms" in e)
    assert resumed_e0 == [2, 3], resumed_e0
    assert latest_epoch(prefix) == 1
    assert checkpoint_meta(prefix, 1)["images_per_dispatch"] == 4

    # Leg 3 — BOUNDARY checkpoint across topologies: resuming the
    # 4-wide epoch-1 save back on the 8-wide mesh must also read the
    # sidecar and rebase the optimizer counters (skip is 0, but the
    # schedule units changed) — the gap the skip-only gating had.
    import logging

    with caplog.at_level(logging.WARNING):
        driver.run_fit(prefix, mesh="8", num_images=16, end_epoch=2,
                       resume="auto")
    assert any("optimizer counters rebased to step 2" in r.message
               for r in caplog.records), [r.message for r in caplog.records
                                          if "rebase" in r.message]
    assert latest_epoch(prefix) == 2
    assert checkpoint_meta(prefix, 2)["images_per_dispatch"] == 8


