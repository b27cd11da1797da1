"""graftquorum — multi-host coordination for the resilience layer.

Every resilience feature before this module (graftguard preemption,
graftheal backend re-acquisition) was gated to single-process runtimes:
emergency saves had no all-host barrier, and a backend loss on one host
left the others deadlocked in a collective. This module supplies the
missing coordination primitives:

- a **KV store** abstraction with two backends: `jax.distributed`'s
  coordination-service client (real pods) and a filesystem directory
  (`FileKVStore`) so N-process CPU tests exercise the REAL protocol —
  atomicity comes from `O_EXCL` create (propose) and `os.replace` (set);
- a deadline-bounded **all-host barrier** that returns the set of hosts
  that arrived (possibly partial — the caller decides whether a partial
  quorum survives via `min_fraction`);
- a first-writer-wins **propose/agree** protocol (the SIGTERM'd host
  proposes the stop boundary; the heal leader proposes the post-heal
  topology) with generation-numbered heal rounds so a host that sleeps
  through round g and wakes in round g+1 discovers it was excluded
  instead of corrupting the new session.

Protocol notes (why two phases for a coordinated stop): hosts in real
SPMD are collective-synchronized and drift by at most one dispatch, but
the CPU simulation runs N fully replicated processes with NO collectives
between them, so drift is unbounded. `CoordinatedStop` therefore agrees
on `max(requested, every host's current boundary)` — phase 1 publishes
each host's floor, phase 2 drains everyone to the max — which is exact
under lockstep and correct under drift.
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Set

from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.resilience import chaos


class QuorumError(RuntimeError):
    """The quorum could not be reached (below min fraction / no store)."""


class QuorumExcludedError(QuorumError):
    """THIS host missed a quorum deadline and the round was sealed
    without it. The correct reaction is a resumable exit (rc 75): the
    surviving quorum carried the run forward and this host's session
    state is stale; it rejoins via ``--resume auto``."""


# ---------------------------------------------------------------------------
# KV stores
# ---------------------------------------------------------------------------

class KVStore:
    """Minimal KV interface the quorum protocol needs.

    ``set`` is last-writer-wins, ``propose`` is first-writer-wins and
    returns the winning value either way. ``get`` is a non-blocking
    peek; blocking waits are built in Quorum via polling so deadline
    handling lives in one place.
    """

    def set(self, key: str, value: str) -> None:
        raise NotImplementedError

    def get(self, key: str) -> Optional[str]:
        raise NotImplementedError

    def propose(self, key: str, value: str) -> str:
        raise NotImplementedError


class FileKVStore(KVStore):
    """Filesystem-backed store: one file per key under ``root``.

    set = write-to-temp + os.replace (atomic on POSIX), propose =
    ``O_CREAT|O_EXCL`` (atomic first-writer-wins), get = read-or-None.
    Keys may contain ``/`` — mapped to subdirectories, so a run's
    namespace is just a directory tree that ``--resume`` debugging can
    inspect with ``cat``.
    """

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        path = os.path.normpath(os.path.join(self.root, key))
        if not path.startswith(os.path.normpath(self.root) + os.sep):
            raise ValueError(f"quorum key escapes store root: {key!r}")
        return path

    def set(self, key: str, value: str) -> None:
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
        try:
            os.write(fd, value.encode("utf-8"))
        finally:
            os.close(fd)
        os.replace(tmp, path)

    def get(self, key: str) -> Optional[str]:
        try:
            with open(self._path(key), "r", encoding="utf-8") as f:
                return f.read()
        except (FileNotFoundError, NotADirectoryError, IsADirectoryError):
            return None

    def propose(self, key: str, value: str) -> str:
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            won = self.get(key)
            if won is None:  # writer crashed between create and write:
                return value  # treat our value as accepted
            return won
        try:
            os.write(fd, value.encode("utf-8"))
        finally:
            os.close(fd)
        return value


class JaxKVStore(KVStore):
    """KV over jax's distributed coordination service client.

    Only reachable after ``jax.distributed.initialize``; constructed via
    :func:`jax_kv_client` which returns None when the runtime is not up
    (callers then fall back to FileKVStore or disable coordination).
    propose() leans on the service rejecting duplicate keys; where the
    installed jax only offers overwrite semantics we emulate
    first-writer-wins with a get-before-set (benign: proposals race only
    between live hosts that would propose compatible values).
    """

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value, allow_overwrite=True)

    def get(self, key: str) -> Optional[str]:
        try:
            value = self._client.key_value_try_get(key)
        except Exception:  # graftlint: disable=broad-except — NOT_FOUND surfaces as a backend-specific runtime error; absent-key is the expected answer here
            return None
        return value if value else None

    def propose(self, key: str, value: str) -> str:
        try:
            self._client.key_value_set(key, value)  # no-overwrite default
            return value
        except Exception:  # graftlint: disable=broad-except — ALREADY_EXISTS (someone else won) surfaces as version-dependent exception types; the get() below recovers the winning value either way
            won = self.get(key)
            return won if won is not None else value


def jax_kv_client():
    """The live coordination-service client, or None.

    Reaches into ``jax._src.distributed.global_state`` — the only place
    jax exposes the KV client today. Version-gated: any import/attr
    failure means "no client" rather than an exception, so CPU tests and
    future jax refactors degrade to the filesystem store.
    """
    try:
        from jax._src import distributed as _dist  # type: ignore

        return getattr(_dist.global_state, "client", None)
    except Exception:  # graftlint: disable=broad-except — version-gated probe into jax._src internals: any import/attr/layout change means "no client", never a crash
        return None


# ---------------------------------------------------------------------------
# the quorum
# ---------------------------------------------------------------------------

@dataclass
class QuorumOutcome:
    """What a heal round agreed on — folded into the heal event."""

    generation: int
    arrived: List[int]
    excluded: List[int]
    devices: int
    spec: str


class Quorum:
    """Deadline-bounded barriers + propose/agree over a KVStore.

    ``index``/``count`` are the host identity (simulated-host wrappers in
    parallel/distributed.py under test, jax.process_index/count on real
    pods). ``active`` starts as the full host set and shrinks when a heal
    round excludes a host — later barriers only wait for active members,
    so one dead host does not deadline every subsequent save.
    """

    def __init__(self, store: KVStore, index: int, count: int, *,
                 timeout_s: float = 60.0, min_fraction: float = 0.5,
                 poll_s: float = 0.05,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 elog=None):
        """``elog``: optional graftscope EventLog (duck-typed; None or a
        NullEventLog both no-op) — every barrier then emits a typed
        ``barrier`` event from this host's view: name, own wait_s
        (monotonic), arrival order over the shared wall stamps, who
        arrived last, timed_out. The grafttower fleet fold (obs/fleet.py)
        attributes the waiters' time to the last arriver and uses the
        releases as its residual-clock-skew correction signal."""
        self.store = store
        self.index = index
        self.count = count
        self.timeout_s = timeout_s
        self.min_fraction = min_fraction
        self.poll_s = poll_s
        self._clock = clock
        self._sleep = sleep
        self.elog = elog
        self.active: Set[int] = set(range(count))

    # -- identity ----------------------------------------------------------

    @property
    def leader(self) -> int:
        """The host that owns publication duties (lowest active index)."""
        return min(self.active)

    def is_leader(self) -> bool:
        return self.index == self.leader

    # -- waits -------------------------------------------------------------

    def wait(self, key: str, timeout_s: Optional[float] = None
             ) -> Optional[str]:
        """Poll ``key`` until present or deadline; None on timeout."""
        deadline = self._clock() + (timeout_s if timeout_s is not None
                                    else self.timeout_s)
        while True:
            value = self.store.get(key)
            if value is not None:
                return value
            if self._clock() >= deadline:
                return None
            self._sleep(self.poll_s)

    def _arrival_stamps(self, prefix: str, arrived: Set[int]
                        ) -> Dict[int, float]:
        """The wall stamps the arrived hosts published (barrier arrivals
        carry ``time.time()`` so arrival ORDER is recoverable across
        hosts — shared-store stamps, comparable to within NTP skew; a
        pre-grafttower "1" value reads as stampless and is skipped:
        it parses as float, so plausibility — a wall stamp is seconds
        since the epoch — is the discriminator, not parseability)."""
        stamps: Dict[int, float] = {}
        for i in arrived:
            raw = self.store.get(f"{prefix}/{i}")
            if raw is None:
                continue
            try:
                stamp = float(raw)
            except ValueError:
                continue
            if stamp >= 1e9:  # Sep 2001 — anything earlier isn't a stamp
                stamps[i] = stamp
        return stamps

    def _emit_barrier(self, name: str, wait_s: float, arrived: Set[int],
                      stamps: Dict[int, float]):
        """One ``barrier`` event from this host's view (obs/fleet.py
        folds all views; the shared stamps make every view agree on
        the arrival order)."""
        if self.elog is None:
            return
        order = sorted(stamps, key=stamps.get)
        self.elog.emit(
            "barrier",
            name=name,
            wait_s=round(wait_s, 4),
            arrived=sorted(arrived),
            absent=sorted(self.active - arrived),
            order=order,
            last=order[-1] if order else None,
            timed_out=not arrived >= self.active)

    def barrier(self, name: str, timeout_s: Optional[float] = None
                ) -> Set[int]:
        """Arrive at ``name`` and wait for the active set; returns who
        arrived by the deadline (a superset check is the caller's job).
        Arrival publishes this host's wall stamp (value = time.time(),
        read back only for presence by the wait loop — order/attribution
        live in the emitted ``barrier`` event).

        Chaos: ``barrier_timeout_at=<site>`` armed for this process makes
        it NOT arrive (simulating a host hung past the deadline) — the
        others then see a partial set, which is exactly the exclusion
        path under test.
        """
        t0 = self._clock()
        if not chaos.site("quorum_barrier"):
            self.store.set(f"{name}/arrive/{self.index}",
                           repr(time.time()))
        deadline = self._clock() + (timeout_s if timeout_s is not None
                                    else self.timeout_s)
        arrived: Set[int] = set()
        while True:
            arrived = {i for i in self.active
                       if self.store.get(f"{name}/arrive/{i}") is not None}
            if arrived >= self.active or self._clock() >= deadline:
                break
            self._sleep(self.poll_s)
        self._emit_barrier(name, self._clock() - t0, arrived,
                           self._arrival_stamps(f"{name}/arrive", arrived))
        return arrived

    def propose(self, name: str, value: str) -> str:
        return self.store.propose(f"{name}/value", value)

    def agree(self, name: str, timeout_s: Optional[float] = None
              ) -> Optional[str]:
        return self.wait(f"{name}/value", timeout_s)

    # -- heal rounds -------------------------------------------------------

    def heal_round(self, generation: int, n_devices: int,
                   agree_spec: Callable[[int, int], str]) -> QuorumOutcome:
        """One generation of the multi-host heal protocol.

        Every surviving host publishes its re-acquired device count and
        waits for the others under the deadline. The leader of the
        arrived set agrees the post-heal topology by calling
        ``agree_spec(min_devices, n_hosts_arrived)`` and seals the round
        with the participant list; everyone else adopts the seal. A host
        that arrives after the seal (its index absent from the sealed
        participants) raises :class:`QuorumExcludedError`; a round whose
        arrived fraction is below ``min_fraction`` raises
        :class:`QuorumError` on every host.
        """
        ns = f"heal/{generation}"
        t0 = self._clock()
        if not chaos.site("quorum_barrier"):
            # Arrival stamp rides NEXT to the device publication: the
            # dev value is protocol payload (parsed as an int below),
            # so the barrier-event wall stamp gets its own key.
            self.store.set(f"{ns}/stamp/{self.index}", repr(time.time()))
            self.store.set(f"{ns}/dev/{self.index}", str(n_devices))
        deadline = self._clock() + self.timeout_s
        while True:
            arrived = {i for i in self.active
                       if self.store.get(f"{ns}/dev/{i}") is not None}
            if arrived >= self.active:
                break
            sealed = self.store.get(f"{ns}/seal")
            if sealed is not None:
                break  # a quorum formed without the stragglers
            if self._clock() >= deadline:
                break
            self._sleep(self.poll_s)
        self._emit_barrier(ns, self._clock() - t0, arrived,
                           self._arrival_stamps(f"{ns}/stamp", arrived))

        sealed = self.store.get(f"{ns}/seal")
        if sealed is None and self.index == min(arrived | {self.index}):
            # Leader of the arrived set: agree + seal. propose() makes a
            # double-leader race (clock skew) converge on one seal.
            devices = min(int(self.store.get(f"{ns}/dev/{i}") or n_devices)
                          for i in arrived) if arrived else n_devices
            spec = agree_spec(devices, max(len(arrived), 1))
            sealed = self.store.propose(f"{ns}/seal", json.dumps({
                "spec": spec, "devices": devices,
                "participants": sorted(arrived | {self.index}),
            }))
        if sealed is None:
            sealed = self.wait(f"{ns}/seal")
        if sealed is None:
            raise QuorumError(
                f"heal generation {generation}: no seal within "
                f"{self.timeout_s:.0f}s (store unreachable or all "
                f"leaders dead)")

        seal = json.loads(sealed)
        participants = set(seal["participants"])
        if self.index not in participants:
            raise QuorumExcludedError(
                f"host {self.index} missed heal generation {generation} "
                f"(quorum sealed with hosts {sorted(participants)}); "
                f"exiting resumable")
        excluded = sorted(self.active - participants)
        if len(participants) < self.min_fraction * self.count:
            raise QuorumError(
                f"heal generation {generation}: only "
                f"{len(participants)}/{self.count} hosts reached the "
                f"quorum (< min fraction {self.min_fraction})")
        self.active = participants
        if excluded:
            logger.warning(
                "quorum: heal generation %d excluded hosts %s "
                "(survivors %s)", generation, excluded,
                sorted(participants))
        return QuorumOutcome(generation=generation,
                             arrived=sorted(participants),
                             excluded=excluded,
                             devices=int(seal["devices"]),
                             spec=str(seal["spec"]))


# ---------------------------------------------------------------------------
# coordinated preemption
# ---------------------------------------------------------------------------

class CoordinatedStop:
    """Two-phase agreement on the emergency-stop dispatch boundary.

    Phase 1 (request): the SIGTERM'd host proposes ``stop/req`` = its
    next boundary. Phase 2 (floor exchange): each host, on first
    observing the request, publishes ``max(req, own boundary)`` and the
    agreed stop is the max over all published floors — no host is asked
    to stop at a boundary it already passed. Hosts then drain to the
    agreed boundary, barrier, and only then does the leader publish the
    ONE emergency save.
    """

    def __init__(self, quorum: Quorum):
        self.quorum = quorum
        self._agreed: Optional[int] = None
        self._published = False

    def request(self, boundary: int) -> None:
        """Propose stopping at ``boundary`` (the signal handler's side)."""
        self.quorum.propose("stop/req", str(boundary))

    def check(self, boundary: int) -> Optional[int]:
        """Poll at a dispatch boundary; returns the agreed stop boundary
        once known (blocking for one floor-exchange round the first
        time a request is seen), else None."""
        if self._agreed is not None:
            return self._agreed
        q = self.quorum
        req = q.store.get("stop/req/value")
        if req is None:
            return None
        if not self._published:
            q.store.set(f"stop/floor/{q.index}", str(max(int(req), boundary)))
            self._published = True
        deadline = q._clock() + q.timeout_s
        floors: Dict[int, int] = {}
        while True:
            floors = {i: int(v) for i in q.active
                      if (v := q.store.get(f"stop/floor/{i}")) is not None}
            if set(floors) >= q.active or q._clock() >= deadline:
                break
            q._sleep(q.poll_s)
        self._agreed = max(list(floors.values()) + [int(req), boundary])
        return self._agreed
