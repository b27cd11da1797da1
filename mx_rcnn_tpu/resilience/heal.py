"""graftheal — in-run backend-loss recovery and elastic-topology resume.

graftguard (resilience/backend.py, preempt.py) made *startup* fault-
tolerant and preemption survivable; this module closes the remaining gap
in the ROADMAP failure list: the backend dying **mid-step**. Before graftheal
a step-time ``UNAVAILABLE`` (hours into a run) was an uncaught
RuntimeError — every step since the last checkpoint
lost, an operator required. Now the train loop's dispatch is wrapped in a
recovery loop (tools/train.py::fit_detector):

1. **Classify.** A step-time RuntimeError is classified with the PR 5
   classification (``classify_backend_error``): transient gRPC markers
   (UNAVAILABLE / DEADLINE_EXCEEDED / ABORTED) heal; anything else — a
   shape error, an INVALID_ARGUMENT — propagates untouched.
2. **Capture.** An in-memory emergency capture of the last known-good
   state: first a *live* capture (``jax.device_get`` of the current
   train state into host-OWNED numpy copies); if the post-loss state is
   unreadable (donated buffers on a dead backend poison the read), fall
   back to the standing host snapshot the loop refreshes every
   ``resilience.heal_snapshot_dispatches`` dispatches — the replayed
   dispatches are re-derived deterministically (epoch order is
   f(seed, epoch), per-dispatch keys fold the global index), so the
   resumed trajectory is the one the uninterrupted run would have taken.
   The refresh is a DEFERRED read (``DeferredSnapshot``): begun at its
   dispatch as one device-side copy of the state with its host
   transfers started, installed at a later dispatch once the copy is
   there, so the loop never waits on the dispatch it has just made.
   Until then the earlier snapshot stands: the replay is bounded by
   ``heal_snapshot_dispatches`` + the dispatches a snapshot is in
   flight (the depth of the device's queue, 10-12 on the chip), and a
   snapshot still pending when the loss lands is dropped, not awaited.
3. **Re-acquire.** Tear the cached backend down (the clear used for the
   CPU-fallback path) and re-acquire through the classified
   retry-with-backoff of ``acquire_backend`` under the SAME
   ``resilience.backend_deadline_s`` that guards startup. What comes
   back must be the platform the run started on: a run on the chip is
   never healed onto a CPU, whatever ``resilience.backend_platform``
   says.
4. **Re-shard.** The backend may come back with a DIFFERENT device
   count (spot reclaim, partial slice): the caller rebuilds the mesh via
   ``parallel.partition.elastic_mesh_spec`` (model axis preserved, data
   axis re-cut to the largest batch-divisible size) and re-derives
   partition specs against the new mesh —
   the GLOBAL batch is invariant, so the loader, the LR schedule and the
   loss trajectory carry straight across the shrink.

Each recovery emits one ``heal`` graftscope event (epoch/dispatch,
classified error, capture mode, downtime, device counts before/after)
and resets the stall watchdog's trailing median — the first post-heal
step pays a fresh compile and must not read as a stall.

Consecutive heals with no completed dispatch in between are capped
(``resilience.heal_consecutive_max``): a fault that recurs instantly is
not an outage, and re-raising beats looping. Fault injection:
``MX_RCNN_CHAOS="device_lost_at_step=K"`` raises the loss signature
before the dispatch that would complete optimizer step K;
``shrink_on_reacquire=N`` hands recovery only the first N devices
(resilience/chaos.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.resilience import chaos
from mx_rcnn_tpu.resilience.backend import (
    _clear_backend_cache,
    acquire_backend,
    classify_backend_error,
)


def _owned(leaf):
    """One leaf as a host-OWNED numpy array (``np.array`` copies)."""
    import jax
    import numpy as np

    return np.array(jax.device_get(leaf))


def host_tree_copy(tree):
    """Host-OWNED numpy copies of a pytree — THE heal-carry invariant:
    ``np.array`` of every leaf, never zero-copy views of runtime buffers
    (the backend they came from is about to be torn down, and on the CPU
    client ``device_get`` can alias the live buffer). Every capture/
    fallback site goes through here so the invariant lives in one place.
    BLOCKING, leaf by leaf: for the callers that need the live state now
    (``recover``'s capture, the starting fallback); the loop's periodic
    snapshot is a ``DeferredSnapshot``, which keeps the same invariant.
    jax imported lazily — this module stays importable without it."""
    import jax

    return jax.tree_util.tree_map(_owned, tree)


def compile_tree_copy(tree):
    """ONE device program that copies every leaf of ``tree`` (device
    arrays) into a fresh buffer of the same sharding, compiled for
    ``tree``'s types without running. The train step donates its state,
    so a snapshot that is read after the next dispatch needs buffers the
    step does not own; this program's inputs are NOT donated, so its
    outputs cannot alias them. Returns the compiled callable."""
    import jax
    import jax.numpy as jnp

    def copy(t):
        return jax.tree_util.tree_map(jnp.copy, t)

    shardings = jax.tree_util.tree_map(lambda x: x.sharding, tree)
    return jax.jit(copy, out_shardings=shardings).lower(tree).compile()


@dataclass
class HealCarry:
    """Host-owned training state at a known-good point — what a session
    is (re)built from. ``params``/``opt_state`` are TREE-form numpy
    copies (never device views: the backend they came from is about to
    be torn down); ``opt_state`` is None only for a fresh run's initial
    carry. ``dispatch`` counts completed dispatches of ``epoch`` —
    ``(epoch, 0)`` is the epoch boundary. ``bag`` is the drained
    MetricBag snapshot at the same point, so the resumed epoch's metrics
    keep accounting for the pre-loss dispatches."""

    params: Any
    opt_state: Any = None
    epoch: int = 0
    dispatch: int = 0
    bag: Optional[Tuple[Dict[str, float], Dict[str, int]]] = None


class DeferredSnapshot:
    """A host read of the train state BEGUN at one dispatch and FINISHED
    over later ones. ``trees`` is ``(params, opt_state)`` as device arrays
    nobody else owns (``compile_tree_copy``'s output, enqueued behind the
    step whose state it copies); their host transfers start here and run
    while the device goes on. ``bag`` is a ``MetricBag.fork()``: the sums
    and the pending device scalars as they stood at that dispatch.
    ``rebase`` (optional, ``opt_state -> opt_state``) is applied to the
    host copy: ``_capture``'s schedule-count normalization.

    ``advance()`` is called once per later dispatch and never waits for
    the device: it turns leaves that are there into host-OWNED copies
    (``host_tree_copy``'s invariant: ``np.array``, never a view of a
    runtime buffer) for at most ``POLL_BUDGET_S`` of the loop's time, so
    what one dispatch pays is bounded whatever the state's size, and
    returns True once all of it is on the host. ``carry()`` then builds
    the ``HealCarry`` of the position the snapshot was TAKEN at."""

    #: the loop's time one dispatch may spend on host copies: under what a
    #: dispatch has to spare on the chip (a 94 ms step against ~40 ms of
    #: host work), so the device's queue stays as full as it was
    POLL_BUDGET_S = 0.04

    def __init__(self, trees, *, epoch: int, dispatch: int, bag=None,
                 rebase: Optional[Callable] = None):
        import jax

        self.epoch, self.dispatch = int(epoch), int(dispatch)
        self._bag, self._rebase = bag, rebase
        leaves, self._treedef = jax.tree_util.tree_flatten(trees)
        self.nbytes = sum(int(x.nbytes) for x in leaves)
        for leaf in leaves:
            leaf.copy_to_host_async()
        self._device = leaves[::-1]  # still to read, the next one last
        self._host = []              # read so far, in leaf order
        #: the Healer's: the run's dispatch count it was taken at, and the
        #: loop's own seconds spent on it so far
        self.taken_at, self.loop_s = 0, 0.0

    def advance(self, clock: Callable[[], float] = time.monotonic) -> bool:
        deadline = clock() + self.POLL_BUDGET_S
        while self._device:
            if not self._device[-1].is_ready():
                return False
            # dropping the device leaf frees its buffer and the runtime's
            # host staging copy as the read goes
            self._host.append(_owned(self._device.pop()))
            if self._device and clock() >= deadline:
                return False
        return self._bag is None or self._bag.ready()

    def carry(self) -> HealCarry:
        import jax

        params, opt_state = jax.tree_util.tree_unflatten(
            self._treedef, self._host)
        if self._rebase is not None:
            opt_state = self._rebase(opt_state)
        return HealCarry(
            params=params, opt_state=opt_state, epoch=self.epoch,
            dispatch=self.dispatch,
            bag=(self._bag.snapshot(ready_only=True)
                 if self._bag is not None else None))


class Healer:
    """The in-run recovery engine fit_detector leans on.

    Holds the standing fallback snapshot, the consecutive-failure cap,
    and the re-acquired device list (``devices`` — None until a heal
    changed the backend; the session builder re-derives the mesh from it
    when set). ``rcfg`` is the ``resilience`` config section; ``elog``
    an optional graftscope EventLog; ``watchdog`` an optional
    StallWatchdog whose trailing median is reset after each heal.
    """

    def __init__(self, rcfg, elog=None, watchdog=None, recorder=None,
                 clock: Callable[[], float] = time.monotonic):
        self.rcfg = rcfg
        self.elog = elog
        self.watchdog = watchdog
        # graftpulse flight recorder (obs/health.py): each heal flushes
        # the last-K-events ring, so the recovery artifact shows the
        # numerics around the loss, not just the heal event.
        self.recorder = recorder
        self._clock = clock
        self.heals = 0
        self.devices = None
        self._consecutive = 0
        self._fallback: Optional[HealCarry] = None
        self._since_snapshot = 0
        # The deferred periodic snapshot (at most one in flight), and the
        # dispatches this run completed (what a snapshot's age counts in).
        self._pending: Optional[DeferredSnapshot] = None
        self._dispatches = 0
        self._n_devices: Optional[int] = None
        self._footprint: Optional[int] = None
        self._platform: Optional[str] = None
        # graftquorum (resilience/quorum.py): multi-host runs install a
        # hook called with the re-acquired device list; it runs one
        # generation of the heal quorum (barrier, topology agreement,
        # exclusion) and returns the QuorumOutcome — the agreed mesh
        # spec the session rebuild must adopt. Raises QuorumExcludedError
        # on the host the quorum moved on without. None = single-host
        # behavior (the session derives the spec locally).
        self.quorum_hook: Optional[Callable] = None
        #: The last heal's QuorumOutcome (None single-host) — the session
        #: rebuild reads the agreed spec from here.
        self.outcome = None

    # -- bookkeeping the train loop drives ---------------------------------

    def note_devices(self, n: int, platform: Optional[str] = None):
        """Record the session's device count (the heal event's 'before')
        and the platform the run is on. The largest session ever seen is
        the run's FOOTPRINT — the cap for reporting re-acquired capacity
        (a re-grow back toward it after an earlier shrink is a real
        transition; spare devices beyond it are not). The FIRST platform
        noted is the run's platform for good: a heal re-acquires that
        one or re-raises (``recover``)."""
        self._n_devices = int(n)
        self._footprint = max(self._footprint or 0, int(n))
        if self._platform is None:
            self._platform = platform

    def note_progress(self):
        """A dispatch completed — the backend is live again; re-arm the
        consecutive-heal cap."""
        self._consecutive = 0
        self._dispatches += 1

    def set_fallback(self, carry: HealCarry):
        """Install/refresh the standing host snapshot (initial carry,
        post-heal carry, or a periodic snapshot)."""
        self._fallback = carry

    def snapshot_due(self) -> bool:
        """True every ``heal_snapshot_dispatches`` completed dispatches
        (0 disables periodic snapshots — live capture only). Due is when
        a snapshot is BEGUN (``begin_snapshot``); it stands as the
        fallback some dispatches later (``poll_snapshot``), so the replay
        after a loss is bounded by this cadence + the dispatches a
        snapshot is in flight."""
        if not self.snapshots:
            return False
        self._since_snapshot += 1
        if self._since_snapshot >= int(self.rcfg.heal_snapshot_dispatches):
            self._since_snapshot = 0
            return True
        return False

    @property
    def snapshots(self) -> bool:
        """Are periodic snapshots on (``heal_snapshot_dispatches`` > 0)?"""
        return int(getattr(self.rcfg, "heal_snapshot_dispatches", 0)) > 0

    @property
    def snapshot_pending(self) -> bool:
        return self._pending is not None

    def begin_snapshot(self, make: Callable[[], DeferredSnapshot]):
        """Begin the periodic snapshot at the dispatch just made, unless
        one is still in flight (a second is never begun: the copy it
        would hold is memory, and the first installs sooner)."""
        if self._pending is not None:
            return
        t0 = self._clock()
        self._pending = snap = make()
        snap.taken_at = self._dispatches
        snap.loop_s = self._clock() - t0

    def poll_snapshot(self):
        """Called once per dispatch: advance the pending snapshot's read
        and install it as the fallback once all of it is on the host;
        until then the earlier fallback stands. Never waits for the
        device, and takes a bounded part of the loop's time."""
        snap = self._pending
        if snap is None:
            return
        t0 = self._clock()
        done = snap.advance(self._clock)
        if done:
            self.set_fallback(snap.carry())
            self._pending = None
        snap.loop_s += self._clock() - t0
        if not done:
            return
        in_flight = self._dispatches - snap.taken_at
        loop_ms = snap.loop_s * 1e3
        logger.info(
            "graftheal: snapshot taken at dispatch %d (epoch %d dispatch "
            "%d) installed %d dispatch(es) later: %.1f MB, %.1f ms of the "
            "loop's time", snap.taken_at, snap.epoch, snap.dispatch,
            in_flight, snap.nbytes / 1e6, loop_ms)
        if self.elog is not None and self.elog.enabled:
            self.elog.emit("snapshot", epoch=snap.epoch,
                           dispatch=snap.dispatch, taken_at=snap.taken_at,
                           in_flight=in_flight, loop_ms=round(loop_ms, 3),
                           bytes=snap.nbytes)

    def drop_snapshot(self):
        """Forget a snapshot in flight without waiting for it (the
        session is ending, or the backend it would be read from is going
        away); the earlier fallback stands."""
        self._pending = None

    # -- the recovery itself ------------------------------------------------

    def healable(self, exc: BaseException) -> bool:
        """Should this step-time error be healed in-process? Transient by
        the PR 5 classification, under the consecutive cap, and heal enabled."""
        if not getattr(self.rcfg, "heal", False):
            return False
        if not isinstance(exc, RuntimeError):
            return False
        if self._consecutive >= max(1, int(self.rcfg.heal_consecutive_max)):
            logger.error(
                "graftheal: %d consecutive heals without a completed "
                "dispatch — the fault recurs instantly, giving up",
                self._consecutive)
            return False
        return classify_backend_error(exc) == "transient"

    def recover(self, exc: BaseException,
                capture: Callable[[], HealCarry]) -> HealCarry:
        """Capture → teardown → re-acquire. Returns the carry to rebuild
        the session from; raises ``exc`` (chained) when no state can be
        captured, and whatever ``acquire_backend`` raises when the
        backend stays down past the deadline.
        """
        t0 = self._clock()
        if self.watchdog is not None:
            # The heal window is a KNOWN no-heartbeat stretch (capture +
            # a possibly hours-long re-acquisition backoff): silence the
            # stall tripwire for its duration — the outage is reported
            # as a `heal` event, not a stall dump (reset() below
            # re-arms).
            self.watchdog.pause()
        self.drop_snapshot()
        mode = "live"
        try:
            carry = capture()
        except Exception as cap_exc:  # noqa: BLE001  # graftlint: disable=broad-except — the post-loss state may be unreadable in arbitrary ways (poisoned futures, donated buffers); ANY capture failure routes to the snapshot fallback
            if self._fallback is None:
                logger.error(
                    "graftheal: live capture failed (%s) and no snapshot "
                    "fallback exists — cannot heal", cap_exc)
                raise exc from cap_exc
            carry = self._fallback
            mode = "snapshot"
            logger.warning(
                "graftheal: live capture failed (%s); rolling back to the "
                "snapshot at epoch %d dispatch %d — the gap replays "
                "deterministically", cap_exc, carry.epoch, carry.dispatch)
        # Teardown: drop jax's cached backend so re-acquisition actually
        # re-initializes (the same clear the silent-CPU-fallback retry
        # path uses) — probing a dead cached client would fail forever.
        _clear_backend_cache()
        devices = acquire_backend(self.rcfg, elog=self.elog)
        devices = chaos.site("backend_reacquire", devices=devices)
        got = {getattr(d, "platform", None) for d in devices}
        if self._platform is not None and self._platform not in got:
            # A run that started on the chip is not "healed" onto
            # whatever comes up once the backends were cleared.
            raise RuntimeError(
                f"graftheal: the run started on {self._platform!r} but "
                f"the backend came back as {sorted(map(str, got))} — "
                "not healing onto another platform") from exc
        # Multi-host: every surviving host reaches the heal quorum with
        # its re-acquired capacity and adopts the agreed topology; a
        # host that missed the deadline gets QuorumExcludedError here
        # (propagates — the survivors sealed the round without it and
        # its only correct move is a resumable exit). Inside the
        # watchdog-paused window: a quorum wait is not a stall.
        self.outcome = None
        if self.quorum_hook is not None:
            self.outcome = self.quorum_hook(devices)
        downtime = self._clock() - t0
        before = self._n_devices
        # The event's "after" is the recovered capacity CAPPED at the
        # run's FOOTPRINT — not at the previous session's (possibly
        # shrunken) size, so a re-grow after an earlier shrink reports
        # as the 4->8 transition it is, while a backend with spare
        # devices beyond the footprint is not called a grow. The exact
        # re-cut mesh is logged by the session rebuild.
        after = (min(len(devices), self._footprint)
                 if self._footprint else len(devices))
        self.heals += 1
        self._consecutive += 1
        self.devices = devices
        self.set_fallback(carry)
        if self.watchdog is not None:
            # The pre-loss trailing median must not judge the first
            # post-heal step (re-acquire + fresh compile): cold grace.
            self.watchdog.reset()
        if self.elog is not None and self.elog.enabled:
            quorum_fields = {}
            if self.outcome is not None:
                # The agreed round, folded into the heal record so the
                # report can show WHO healed together and what topology
                # they agreed on (the event also carries this process's
                # index via the EventLog process stamp).
                quorum_fields = dict(
                    quorum_generation=self.outcome.generation,
                    quorum_hosts=self.outcome.arrived,
                    quorum_excluded=self.outcome.excluded,
                    quorum_devices=self.outcome.devices,
                    quorum_spec=self.outcome.spec)
            self.elog.emit("heal", epoch=carry.epoch, dispatch=carry.dispatch,
                           error=str(exc)[:500], mode=mode,
                           downtime_s=round(downtime, 3),
                           devices_before=before,
                           devices_after=after, **quorum_fields)
        if self.recorder is not None:
            self.recorder.dump("heal")
        logger.warning(
            "graftheal: healed step-time backend loss at epoch %d dispatch "
            "%d (%s capture, %.1fs down, devices %s -> %d): %s",
            carry.epoch, carry.dispatch, mode, downtime, before, after, exc)
        return carry
