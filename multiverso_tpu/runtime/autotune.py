"""Closed-loop self-tuning: ClusterMetrics drives the performance knobs.

The repo exposes dozens of load-bearing flags (staleness bound, replica
budget, coalescing flush caps, admission watermarks, serving batch
window, allreduce chunk, codec density threshold) and — since the
observability layer (docs/OBSERVABILITY.md) — the cluster-wide signals
to judge them. This module closes the loop (docs/AUTOTUNE.md): the
rank-0 controller's ``AutotuneManager`` consumes the aggregated
``ClusterMetrics`` view on a ``-autotune_interval_s`` cadence, runs one
policy per knob (hysteresis + hard min/max guardrails), and broadcasts
epoch-stamped config updates as ``Control_Config`` messages — the
``Control_Shard_Map`` pattern: below the worker band, intercepted by
name in the communicator, remote copies on non-blocking ``send_async``
(the recurring dispatch-starvation lesson). The receive side is the
dynamic-flag layer in ``util/configure.py`` (``TUNABLE_FLAGS`` +
per-flag apply hooks), so hot paths that cached a value at
construction actually pick the change up; non-tunable flags are
rejected at broadcast time.

Every decision is observable: ``mv_autotune_*`` gauges ride the
controller's ``/metrics`` scrape surface (current value, last-change
epoch, latest policy verdict, per-rank acked epoch), and the full
decision trajectory is kept (``AutotuneManager.trajectory``).

Adaptive-decision precedent: SparCML's density break-even and EQuARX's
quantization-tier selection (PAPERS.md) pick their operating point from
measured traffic rather than a pinned constant — here the same move is
applied across the whole transport/table/serving stack.
"""

from __future__ import annotations

import collections
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core.blob import Blob
from ..core.message import Message, MsgType
from ..util import log
from ..util.configure import (CANONICAL_FLAGS, define_double,
                              define_string, get_flag)
from ..util.dashboard import count
from ..util.lock_witness import named_condition, named_lock
from . import actor as actors
from . import thread_roles

define_double("autotune_interval_s", 0.0,
              "closed-loop self-tuning cadence ON THE CONTROLLER RANK "
              "(docs/AUTOTUNE.md): every interval the AutotuneManager "
              "evaluates the aggregated ClusterMetrics view against "
              "the per-knob policies and broadcasts an epoch-stamped "
              "Control_Config update when any knob moves. 0 (default) "
              "disables the controller — every knob stays at its "
              "flag-configured value. Pair with -metrics_interval_s "
              "(the policies are blind without rank reports)")
define_double("autotune_slo_p99_ms", 50.0,
              "read-latency SLO the autotune policies steer against: "
              "the serving p99 (SERVING_LATENCY_MS, falling back to "
              "the mean blocking table-Get when no serving tier runs) "
              "inside this bound permits throughput-side widening "
              "(staleness bound); a violation drives the shrink side "
              "(docs/AUTOTUNE.md)")
define_string("autotune_pin", "",
              "comma-separated tunable flag names the autotune "
              "controller must NOT move (operator override, read "
              "live each tick): pinned knobs keep their current "
              "value and report verdict 'pinned' in the "
              "mv_autotune_* gauges")

#: POLICY REGISTRY — one entry per knob the controller actively
#: drives, with its hard guardrail bounds and the canonical metrics it
#: reads. ``tools/mvlint``'s tunable-lint pass parses this literal
#: (never imports) and fails CI when a key is not in
#: ``util/configure.py TUNABLE_FLAGS`` or a ``metrics`` entry does not
#: name a canonical metric (``util/dashboard.py METRIC_NAMES``,
#: trailing-``*`` families included) — a policy steering on a typo'd
#: signal would silently hold forever. Keep the literal plain.
#: ``TUNABLE_FLAGS`` entries WITHOUT a policy here are broadcast-able
#: (rejoin re-anchoring, tests) but never moved autonomously.
AUTOTUNE_POLICIES: Dict[str, dict] = {
    "max_get_staleness": {
        "min": 0, "max": 64,
        "metrics": ["SERVING_LATENCY_MS", "WORKER_TABLE_SYNC_GET",
                    "SERVER_PROCESS_GET", "WORKER_PROCESS_GET",
                    "CLIENT_CACHE_HIT", "CLIENT_CACHE_MISS"],
    },
    "replica_hot_rows": {
        "min": 0, "max": 4096,
        "metrics": ["REPLICA_REPAIR", "REPLICA_HIT",
                    "SERVER_PROCESS_GET"],
    },
    "coalesce_max_msgs": {
        "min": 8, "max": 64,
        "metrics": ["DISPATCH_QUEUE_DEPTH[d*]", "MAILBOX_DEPTH[*]"],
    },
    "serving_batch_window_ms": {
        "min": 0.25, "max": 2.0,
        "metrics": ["DISPATCH_QUEUE_DEPTH[d*]", "MAILBOX_DEPTH[*]",
                    "SERVING_LATENCY_MS"],
    },
    "allreduce_chunk_kb": {
        "min": 64, "max": 4096,
        "metrics": ["tcp_send"],
    },
    "wire_codec_density": {
        "min": 0.05, "max": 0.9,
        "metrics": ["SPARSE_FILL[*]"],
    },
}

#: Hysteresis: a knob moves only after this many CONSECUTIVE ticks
#: proposing the same direction — one noisy window must not flap a
#: knob the whole cluster re-applies.
HYSTERESIS_TICKS = 2
#: Cooldown: after a knob moves, it holds for this many ticks so the
#: next decision sees metrics produced UNDER the new value, not the
#: transition.
COOLDOWN_TICKS = 2
#: Below this many table Gets per tick the read-side policies hold —
#: an idle cluster teaches nothing.
MIN_READ_RATE = 32
#: Queue-depth watermarks (p90 of the dispatch/mailbox depth samples)
#: for the back-off policies.
QUEUE_DEEP = 64.0
QUEUE_SHALLOW = 8.0
#: tcp_send mean-ms thresholds for the allreduce chunk step.
SEND_SLOW_MS = 4.0
SEND_FAST_MS = 0.5
#: Decision-trajectory retention.
TRAJECTORY_CAP = 512


# -- signal extraction (pure functions over a cluster_view dict) --

def merged_sample(view: Dict, name: str, field: str) -> Optional[float]:
    snap = (view.get("samples_merged") or {}).get(name)
    if not snap or field not in snap:
        return None
    return float(snap[field])


def family_sample_max(view: Dict, prefix: str,
                      field: str) -> Optional[float]:
    """Max of ``field`` across every merged sample family instance
    whose name starts with ``prefix`` (``DISPATCH_QUEUE_DEPTH[d`` →
    the deepest destination)."""
    best = None
    for name, snap in (view.get("samples_merged") or {}).items():
        if name.startswith(prefix) and field in snap:
            value = float(snap[field])
            if best is None or value > best:
                best = value
    return best


def monitor_totals(view: Dict, name: str) -> Tuple[int, float]:
    agg = (view.get("monitors_sum") or {}).get(name) or {}
    return int(agg.get("count", 0)), float(agg.get("elapsed_ms", 0.0))


class AutotuneManager:
    """Rank-0 closed-loop knob controller (docs/AUTOTUNE.md).

    Constructed unconditionally with the controller actor (cheap); the
    evaluation thread only starts when ``-autotune_interval_s > 0``.
    ``evaluate`` is exposed for tests — it is the code the thread's
    ``tick_once`` runs.
    """

    def __init__(self, zoo, cluster_metrics) -> None:
        from ..util import configure
        self._zoo = zoo
        self._metrics = cluster_metrics
        self._state_lock = named_lock(f"autotune[r{zoo.rank}].state")
        # Epoch continues from whatever this process already applied:
        # a fresh manager (a second ``mv.init`` in one process) must outrank the previous
        # run's broadcasts or its first update would be ignored as a
        # replay.
        self._epoch = configure.applied_config_epoch()  # guarded_by: _state_lock
        #: Cumulative knob map (every change ever broadcast): each
        #: broadcast carries the FULL map so a rank that missed an
        #: epoch converges from any later one, and a rejoined rank
        #: re-anchors from a single re-broadcast.
        self._config: Dict[str, Any] = {}  # guarded_by: _state_lock
        # _tick/_streak/_last_change/_prev_counts are tick-thread-only
        # working state (tick_once callers serialize); not annotated.
        self._tick = 0
        self._streak: Dict[str, Tuple[str, int]] = {}
        self._last_change: Dict[str, int] = {}
        self._gauges: Dict[str, Dict] = {}  # guarded_by: _state_lock
        self._acked: Dict[int, int] = {}  # guarded_by: _state_lock
        self._trajectory: collections.deque = collections.deque(  # guarded_by: _state_lock
            maxlen=TRAJECTORY_CAP)
        # Monotonic decision count for the exported counter — the
        # trajectory deque is capped, so its len() would freeze.
        self._decisions_total = 0  # guarded_by: _state_lock
        # Previous cumulative monitor totals, for per-tick deltas.
        self._prev_counts: Dict[str, Tuple[int, float]] = {}
        self._stop_cond = named_condition(f"autotune[r{zoo.rank}].stop")
        self._stopped = False  # guarded_by: _stop_cond
        self._thread: Optional[threading.Thread] = None
        self._policies = {
            "max_get_staleness": self._policy_staleness,
            "replica_hot_rows": self._policy_replica,
            "coalesce_max_msgs": self._policy_coalesce,
            "serving_batch_window_ms": self._policy_batch_window,
            "allreduce_chunk_kb": self._policy_allreduce_chunk,
            "wire_codec_density": self._policy_codec_density,
        }

    # -- lifecycle --
    def start(self) -> None:
        interval = float(get_flag("autotune_interval_s"))
        if interval <= 0 or self._thread is not None:
            return
        self._thread = thread_roles.spawn(
            thread_roles.BACKGROUND, target=self._main,
            args=(interval,), name=f"mv-autotune-r{self._zoo.rank}")

    def stop(self) -> None:
        with self._stop_cond:
            self._stopped = True
            self._stop_cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _main(self, interval: float) -> None:
        while True:
            with self._stop_cond:
                if self._stopped:
                    return
                self._stop_cond.wait(timeout=interval)
                if self._stopped:
                    return
            try:
                self.tick_once()
            except Exception as exc:  # noqa: BLE001 - a bad tick
                # (teardown race, malformed view) loses one decision
                # window, never the controller
                import traceback
                log.error("autotune: tick failed: %s\n%s", exc,
                          traceback.format_exc())

    # -- one evaluation round --
    def tick_once(self) -> Dict[str, Any]:
        """Evaluate every policy against the current cluster view and
        broadcast the changes (if any). Returns the changed-knob map."""
        view = self._metrics.cluster_view()
        changes = self.evaluate(view)
        if changes:
            self._broadcast(changes)
        return changes

    def evaluate(self, view: Dict) -> Dict[str, Any]:
        """Policy pass over one cluster view: per-knob verdicts with
        hysteresis, cooldown and guardrail clamping. Updates the
        gauge/trajectory state; returns {knob: new_value} for knobs
        that should change NOW."""
        self._tick += 1
        sig = self._signals(view)
        pinned = {p.strip() for p in
                  str(get_flag("autotune_pin")).split(",") if p.strip()}
        changes: Dict[str, Any] = {}
        for knob, policy in self._policies.items():
            # Canonical-default fallback: a knob whose defining module
            # is not imported in this process (e.g. the allreduce
            # engine in a serving-only deployment) still evaluates.
            cur = get_flag(knob, CANONICAL_FLAGS[knob])
            if knob in pinned:
                # Reset the hysteresis streak too: a pre-pin verdict
                # must not survive the pin as a stale first vote that
                # lets one fresh observation move the knob on unpin.
                self._streak[knob] = ("pinned", 0)
                self._note(knob, cur, "pinned", "operator pin "
                           "(-autotune_pin)")
                continue
            bounds = AUTOTUNE_POLICIES[knob]
            if not bounds["min"] <= cur <= bounds["max"]:
                # The operator configured a value OUTSIDE the policy's
                # band (e.g. -serving_batch_window_ms=0 = batching
                # disabled): clamping it back in would let a "down"
                # verdict RAISE the knob and re-enable what was
                # explicitly turned off. Out-of-band means
                # operator-managed — hands off, like a pin.
                self._streak[knob] = ("unmanaged", 0)
                self._note(knob, cur, "unmanaged",
                           "value outside the policy band "
                           f"[{bounds['min']}, {bounds['max']}] — "
                           "operator-set, not touched")
                continue
            proposed, verdict, reason = policy(cur, sig)
            proposed = self._clamp(knob, proposed, bounds)
            if proposed == cur and verdict in ("up", "down"):
                # Clamped back onto the current value: the knob sits
                # at its guardrail in the proposed direction.
                verdict, reason = "hold", reason + " (at guardrail)"
            if self._gate(knob, verdict):
                changes[knob] = proposed
                self._last_change[knob] = self._tick
                with self._state_lock:
                    self._trajectory.append({
                        "tick": self._tick,
                        "time": round(time.time(), 3),
                        "epoch": self._epoch + 1,
                        "knob": knob, "from": cur, "to": proposed,
                        "verdict": verdict, "reason": reason})
                self._note(knob, proposed, verdict, reason,
                           changed=True)
            else:
                self._note(knob, cur, verdict, reason)
        return changes

    def _clamp(self, knob: str, value: Any, bounds: dict) -> Any:
        lo, hi = bounds["min"], bounds["max"]
        value = min(max(value, lo), hi)
        if isinstance(CANONICAL_FLAGS[knob], int):
            value = int(round(value))
        return value

    def _gate(self, knob: str, verdict: str) -> bool:
        """Hysteresis + cooldown: act only after HYSTERESIS_TICKS
        consecutive same-direction verdicts, and never within
        COOLDOWN_TICKS of the knob's last change."""
        if verdict not in ("up", "down"):
            self._streak[knob] = (verdict, 0)
            return False
        prev, n = self._streak.get(knob, ("", 0))
        n = n + 1 if prev == verdict else 1
        self._streak[knob] = (verdict, n)
        if n < HYSTERESIS_TICKS:
            return False
        if self._tick - self._last_change.get(knob, -10**9) \
                < COOLDOWN_TICKS:
            return False
        return True

    def _note(self, knob: str, value: Any, verdict: str, reason: str,
              changed: bool = False) -> None:
        with self._state_lock:
            ent = self._gauges.setdefault(knob, {"last_epoch": 0})
            ent.update(value=value, verdict=verdict, reason=reason)
            if changed:
                ent["last_epoch"] = self._epoch + 1

    # -- signals --
    def _signals(self, view: Dict) -> Dict[str, Any]:
        """Extract every policy input from one cluster view; monitor
        counters are converted to per-tick deltas against the previous
        view (first tick: all deltas None → every policy holds)."""
        deltas: Dict[str, Optional[Tuple[int, float]]] = {}
        for name in ("WORKER_PROCESS_GET", "WORKER_TABLE_SYNC_GET",
                     "CLIENT_CACHE_HIT", "CLIENT_CACHE_MISS",
                     "REPLICA_REPAIR", "REPLICA_HIT",
                     "SERVER_PROCESS_GET", "tcp_send"):
            total = monitor_totals(view, name)
            prev = self._prev_counts.get(name)
            self._prev_counts[name] = total
            if prev is None or total[0] < prev[0]:
                # First tick, or a counter regression (rank restarted
                # and re-reported from zero): no trustworthy delta.
                deltas[name] = None
            else:
                deltas[name] = (total[0] - prev[0],
                                total[1] - prev[1])

        def delta_count(name: str) -> Optional[int]:
            d = deltas[name]
            return None if d is None else d[0]

        def delta_mean_ms(name: str) -> Optional[float]:
            d = deltas[name]
            if d is None or d[0] <= 0:
                return None
            return d[1] / d[0]

        queue_p90 = max(
            family_sample_max(view, "DISPATCH_QUEUE_DEPTH[", "p90")
            or 0.0,
            family_sample_max(view, "MAILBOX_DEPTH[", "p90") or 0.0)
        return {
            "slo_ms": float(get_flag("autotune_slo_p99_ms")),
            "serving_p99_ms": merged_sample(
                view, "SERVING_LATENCY_MS", "p99"),
            "get_mean_ms": delta_mean_ms("WORKER_TABLE_SYNC_GET"),
            "server_get_mean_ms": delta_mean_ms("SERVER_PROCESS_GET"),
            "get_rate": delta_count("WORKER_PROCESS_GET"),
            "hit_delta": delta_count("CLIENT_CACHE_HIT"),
            "miss_delta": delta_count("CLIENT_CACHE_MISS"),
            "repair_delta": delta_count("REPLICA_REPAIR"),
            "replica_hit_delta": delta_count("REPLICA_HIT"),
            "server_get_delta": delta_count("SERVER_PROCESS_GET"),
            "send_mean_ms": delta_mean_ms("tcp_send"),
            "send_delta": delta_count("tcp_send"),
            "queue_p90": queue_p90,
            "input_density_p50": merged_sample(
                view, "SPARSE_FILL[input]", "p50"),
        }

    # -- per-knob policies --
    def _policy_staleness(self, cur, sig):
        """Widen the client-cache staleness bound while the read p99
        is inside the SLO (trading bounded staleness for locally
        served reads); shrink on violation. Serving p99 when a
        frontend reports; else the mean blocking-Get; else the
        server-side get handling mean (a training-only cluster's
        nearest read-latency signal)."""
        p99 = sig["serving_p99_ms"]
        if p99 is None:
            p99 = sig["get_mean_ms"]
        if p99 is None:
            p99 = sig["server_get_mean_ms"]
        rate = sig["get_rate"]
        if p99 is None or rate is None or rate < MIN_READ_RATE:
            # "idle", not "hold": hold means "judged at its operating
            # point"; a quiet window judges nothing.
            return cur, "idle", "no read traffic to judge"
        if p99 > sig["slo_ms"]:
            return cur // 2, "down", (
                f"read p99 {p99:.1f}ms over the "
                f"{sig['slo_ms']:.0f}ms SLO")
        hits = sig["hit_delta"] or 0
        misses = sig["miss_delta"] or 0
        if cur > 0 and hits + misses >= MIN_READ_RATE \
                and misses <= 0.05 * (hits + misses):
            return cur, "hold", "cache already absorbing the reads"
        return (cur * 2 if cur else 4), "up", (
            f"read p99 {p99:.1f}ms inside the "
            f"{sig['slo_ms']:.0f}ms SLO with uncached read traffic")

    def _policy_replica(self, cur, sig):
        """Grow the hot-row replica budget when owners are fielding
        repair traffic (hot reads missing their replica floor);
        shrink it back once replica traffic goes quiet."""
        repairs = sig["repair_delta"]
        gets = sig["server_get_delta"]
        if repairs is None or gets is None:
            return cur, "hold", "no report delta yet"
        if repairs >= 8 and repairs > 0.01 * max(gets, 1):
            return max(cur * 2, 64), "up", (
                f"{repairs} repairs against {gets} server gets this "
                f"window")
        if cur > 0 and repairs == 0 \
                and (sig["replica_hit_delta"] or 0) == 0:
            return cur // 2, "down", "replica tier idle this window"
        return cur, "hold", "repair rate nominal"

    def _policy_coalesce(self, cur, sig):
        """Back off the coalescing flush caps while outbound send
        queues sit deep (staged adds behind a deep queue only add
        latency); restore toward the canonical default when
        shallow."""
        depth = sig["queue_p90"]
        default = CANONICAL_FLAGS["coalesce_max_msgs"]
        if depth > QUEUE_DEEP and cur > 8:
            return cur // 2, "down", (
                f"dispatch/mailbox depth p90 {depth:.0f} over "
                f"{QUEUE_DEEP:.0f}")
        if depth < QUEUE_SHALLOW and cur < default:
            return min(cur * 2, default), "up", (
                f"queues shallow (p90 {depth:.0f}); restoring toward "
                f"the default")
        return cur, "hold", f"depth p90 {depth:.0f} in band"

    def _policy_batch_window(self, cur, sig):
        """Back off the serving batch window when the queues behind
        the reads sit deep or the serving p99 violates the SLO (the
        window is pure added latency then); restore toward the
        canonical default when healthy."""
        depth = sig["queue_p90"]
        p99 = sig["serving_p99_ms"]
        default = CANONICAL_FLAGS["serving_batch_window_ms"]
        if depth > QUEUE_DEEP or (p99 is not None
                                  and p99 > sig["slo_ms"]):
            return cur / 2, "down", (
                f"depth p90 {depth:.0f} / serving p99 "
                f"{p99 if p99 is not None else float('nan'):.1f}ms")
        if cur < default and depth < QUEUE_SHALLOW \
                and (p99 is None or p99 < sig["slo_ms"] / 2):
            return min(cur * 2, default), "up", (
                "healthy; restoring toward the default window")
        return cur, "hold", "window at its operating point"

    def _policy_allreduce_chunk(self, cur, sig):
        """Step the allreduce chunk toward the wire's measured
        break-even: long per-frame sends mean the chunk serializes too
        much behind one socket write; very short ones mean per-frame
        overhead dominates."""
        mean = sig["send_mean_ms"]
        if mean is None or (sig["send_delta"] or 0) < 16:
            return cur, "hold", "too few wire sends to judge"
        if mean > SEND_SLOW_MS:
            return cur // 2, "down", (
                f"mean wire send {mean:.2f}ms over "
                f"{SEND_SLOW_MS:.1f}ms")
        if mean < SEND_FAST_MS:
            return cur * 2, "up", (
                f"mean wire send {mean:.2f}ms under "
                f"{SEND_FAST_MS:.1f}ms")
        return cur, "hold", f"mean wire send {mean:.2f}ms in band"

    def _policy_codec_density(self, cur, sig):
        """Track the sparse/dense break-even the collectives actually
        observe: keep the codec's dense-switchover threshold a margin
        above the measured input density, so genuinely sparse traffic
        stays sparse and fill-in switches dense (SparCML's density
        break-even, PAPERS.md)."""
        density = sig["input_density_p50"]
        if density is None:
            return cur, "hold", "no sparse-traffic density samples"
        target = density + 0.15
        if abs(target - cur) <= 0.1:
            return cur, "hold", (
                f"threshold within 0.1 of measured density "
                f"{density:.2f}+margin")
        step = cur + (target - cur) / 2
        return round(step, 3), ("up" if target > cur else "down"), (
            f"measured input density p50 {density:.2f}; stepping "
            f"toward {target:.2f}")

    # -- broadcast (the Control_Shard_Map pattern) --
    def _broadcast(self, changes: Dict[str, Any]) -> None:
        with self._state_lock:
            self._config.update(changes)
            self._epoch += 1
            self._decisions_total += len(changes)
            epoch = self._epoch
            flags = dict(self._config)
        count("AUTOTUNE_DECISION", len(changes))
        log.info("autotune: epoch %d — %s", epoch,
                 {k: changes[k] for k in sorted(changes)})
        self._send_config(epoch, flags)

    def broadcast_current(self) -> None:
        """Re-send the cumulative config at the current epoch — the
        rejoin path: a late-joining (restarted) rank registered with
        construction-time flag values and must re-anchor on the live
        config without waiting for the next knob move. Idempotent
        everywhere else (epoch regression is ignored on apply)."""
        with self._state_lock:
            epoch = self._epoch
            flags = dict(self._config)
        if not flags:
            return
        self._send_config(epoch, flags)

    def _send_config(self, epoch: int, flags: Dict[str, Any]) -> None:
        from ..util.configure import TUNABLE_FLAGS
        bad = sorted(n for n in flags if n not in TUNABLE_FLAGS)
        if bad:  # the broadcast-time rejection, controller side
            raise KeyError(
                f"autotune: refusing to broadcast non-tunable "
                f"flag(s) {bad}")
        payload = json.dumps({"epoch": int(epoch), "flags": flags})
        blob = np.frombuffer(payload.encode(), dtype=np.uint8).copy()
        dead = self._dead_ranks()
        for dst in range(self._zoo.net_size):
            if dst in dead:
                continue  # its rejoin re-register gets a re-broadcast
            msg = Message(src=self._zoo.rank, dst=dst,
                          msg_type=MsgType.Control_Config)
            msg.push(Blob(blob.copy()))
            if dst == self._zoo.rank:
                # Local delivery through the communicator's forward
                # path (a mailbox push, never blocks) — the same
                # routing remote ranks take, so one code path applies
                # configs everywhere.
                self._zoo.send_to(actors.COMMUNICATOR, msg)
                continue
            try:
                self._zoo.net.send_async(msg)
            except Exception as exc:  # noqa: BLE001 - an unreachable
                # rank re-anchors from the next broadcast or its
                # rejoin; its failure must not kill the controller.
                log.debug("autotune: config broadcast to rank %d "
                          "failed: %s", dst, exc)

    def _dead_ranks(self) -> set:
        controller = self._zoo._actors.get(actors.CONTROLLER)
        if controller is None:
            return set()
        with controller._live_lock:
            return set(controller._declared_dead)

    # -- acks / observability --
    def note_ack(self, rank: int, epoch: int) -> None:
        with self._state_lock:
            if epoch >= self._acked.get(rank, -1):
                self._acked[rank] = int(epoch)

    def acked_epochs(self) -> Dict[int, int]:
        with self._state_lock:
            return dict(self._acked)

    @property
    def epoch(self) -> int:
        with self._state_lock:
            return self._epoch

    def trajectory(self) -> List[Dict]:
        """Every applied decision, oldest first."""
        with self._state_lock:
            return list(self._trajectory)

    def gauges(self) -> Dict[str, Dict]:
        with self._state_lock:
            return {k: dict(v) for k, v in self._gauges.items()}

    def prometheus_text(self) -> str:
        """The ``mv_autotune_*`` gauge block appended to the
        controller's ``/metrics`` exposition (docs/AUTOTUNE.md):
        config epoch, per-knob current value / last-change epoch /
        verdict, per-rank acked epoch, total decisions."""
        from .metrics import _escape_label, _fmt
        with self._state_lock:
            epoch = self._epoch
            gauges = {k: dict(v) for k, v in self._gauges.items()}
            acked = dict(self._acked)
            decisions = self._decisions_total
        lines = [
            "# HELP mv_autotune_config_epoch latest epoch-stamped "
            "config broadcast by the autotune controller",
            "# TYPE mv_autotune_config_epoch gauge",
            f"mv_autotune_config_epoch {epoch}",
            "# HELP mv_autotune_decisions_total knob changes the "
            "autotune controller has broadcast (monotonic)",
            "# TYPE mv_autotune_decisions_total counter",
            f"mv_autotune_decisions_total {decisions}",
            "# HELP mv_autotune_value current value of an autotuned "
            "knob as the controller last evaluated it",
            "# TYPE mv_autotune_value gauge",
        ]
        for knob in sorted(gauges):
            lines.append(
                f'mv_autotune_value{{knob="{_escape_label(knob)}"}} '
                f'{_fmt(float(gauges[knob].get("value", 0)))}')
        lines += [
            "# HELP mv_autotune_last_epoch config epoch of a knob's "
            "most recent change (0 = never moved)",
            "# TYPE mv_autotune_last_epoch gauge",
        ]
        for knob in sorted(gauges):
            lines.append(
                f'mv_autotune_last_epoch{{knob='
                f'"{_escape_label(knob)}"}} '
                f'{int(gauges[knob].get("last_epoch", 0))}')
        lines += [
            "# HELP mv_autotune_verdict latest policy verdict per "
            "knob (1 on the active verdict label)",
            "# TYPE mv_autotune_verdict gauge",
        ]
        for knob in sorted(gauges):
            verdict = str(gauges[knob].get("verdict", "hold"))
            lines.append(
                f'mv_autotune_verdict{{knob="{_escape_label(knob)}",'
                f'verdict="{_escape_label(verdict)}"}} 1')
        lines += [
            "# HELP mv_autotune_rank_epoch config epoch each rank "
            "last acked (config convergence per rank)",
            "# TYPE mv_autotune_rank_epoch gauge",
        ]
        for rank in sorted(acked):
            lines.append(
                f'mv_autotune_rank_epoch{{rank="{rank}"}} '
                f'{acked[rank]}')
        return "\n".join(lines) + "\n"
