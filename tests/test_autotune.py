"""Closed-loop self-tuning tests (runtime/autotune.py, docs/AUTOTUNE.md).

Covers the dynamic-flag layer (util/configure.py TUNABLE_FLAGS +
apply hooks: hooks fire on broadcast with coerced values, non-tunable
flags are rejected atomically, config-epoch regression is ignored,
weakly-held hooks unregister with their owner), the Control_Config
broadcast/ack round trip through the communicator, the rejoin
re-anchor (a late-joining rank receives the current config epoch on
register), the AutotuneManager policies (SLO-gated staleness widening/
shrinking, hysteresis, cooldown, pinning, guardrail clamping), the
live retune of construction-time caches (row cache activation,
admission watermarks, batch window), and the ClusterMetrics ingest
hardening (out-of-order/stale report dropping keyed on incarnation +
sequence).
"""

import gc
import threading
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import Message, MsgType
from multiverso_tpu.runtime import actor as actors
from multiverso_tpu.runtime.autotune import (AUTOTUNE_POLICIES,
                                             AutotuneManager)
from multiverso_tpu.util import configure
from multiverso_tpu.util.configure import (CANONICAL_FLAGS,
                                           TUNABLE_FLAGS, get_flag,
                                           register_tunable_hook,
                                           set_flag)
from multiverso_tpu.util.dashboard import METRIC_NAMES


@pytest.fixture
def env():
    mv.init([])
    yield
    mv.shutdown()


def _next_epoch(k: int = 1) -> int:
    """An epoch guaranteed to advance this process's applied
    watermark (the watermark is process-global and monotonic across
    tests)."""
    return configure.applied_config_epoch() + k


# ---------------------------------------------------------------------------
# The registries


class TestRegistries:
    def test_every_tunable_is_canonical(self):
        assert set(TUNABLE_FLAGS) <= set(CANONICAL_FLAGS)

    def test_every_policy_drives_a_tunable(self):
        assert set(AUTOTUNE_POLICIES) <= set(TUNABLE_FLAGS)

    def test_policy_metrics_are_canonical(self):
        from tools.mvlint.metric_lint import family_match
        for knob, policy in AUTOTUNE_POLICIES.items():
            for metric in policy["metrics"]:
                assert family_match(metric, METRIC_NAMES), \
                    (knob, metric)

    def test_policy_bounds_are_sane(self):
        for knob, policy in AUTOTUNE_POLICIES.items():
            assert policy["min"] <= policy["max"], knob
            default = CANONICAL_FLAGS[knob]
            assert policy["min"] <= default <= policy["max"], \
                (knob, default)


# ---------------------------------------------------------------------------
# The dynamic-flag layer


class TestDynamicFlagLayer:
    def test_register_hook_rejects_non_tunable(self):  # mvlint: ignore[tunable-lint]
        with pytest.raises(KeyError):  # the rejection under test
            register_tunable_hook("port", lambda v: None)

    def test_apply_tunable_fires_hook_with_coerced_value(self):
        seen = []
        register_tunable_hook("coalesce_max_msgs", seen.append)
        configure.apply_tunable("coalesce_max_msgs", "32")  # str in
        assert seen == [32]  # int out (canonical type coercion)
        assert get_flag("coalesce_max_msgs") == 32

    def test_apply_tunable_rejects_non_tunable(self):
        with pytest.raises(KeyError):
            configure.apply_tunable("port", 1234)

    def test_apply_config_epoch_regression_ignored(self):
        e = _next_epoch()
        assert configure.apply_config(
            e, {"coalesce_max_msgs": 16}) is True
        assert get_flag("coalesce_max_msgs") == 16
        # Same epoch replayed, and an older epoch: both no-ops.
        assert configure.apply_config(
            e, {"coalesce_max_msgs": 48}) is False
        assert configure.apply_config(
            e - 1, {"coalesce_max_msgs": 48}) is False
        assert get_flag("coalesce_max_msgs") == 16
        assert configure.applied_config_epoch() == e

    def test_apply_config_rejects_non_tunable_atomically(self):
        before = get_flag("coalesce_max_msgs")
        with pytest.raises(KeyError):
            configure.apply_config(_next_epoch(), {
                "coalesce_max_msgs": 8,   # tunable ...
                "port": 1234,             # ... but this is not
            })
        # NOTHING applied, watermark unmoved: a broadcast naming a
        # non-tunable flag is refused whole, never half-applied.
        assert get_flag("coalesce_max_msgs") == before

    def test_apply_config_rejects_bad_value_atomically(self):
        # A garbage VALUE (version skew / controller bug) must refuse
        # the whole update before the watermark moves, so a corrected
        # re-broadcast at the SAME epoch still lands.
        before = get_flag("coalesce_max_msgs")
        watermark = configure.applied_config_epoch()
        epoch = _next_epoch()
        with pytest.raises(ValueError):
            configure.apply_config(epoch, {
                "coalesce_max_msgs": 24,
                "max_get_staleness": "not-an-int"})
        assert get_flag("coalesce_max_msgs") == before
        assert configure.applied_config_epoch() == watermark
        # The epoch was not burned: the corrected broadcast applies.
        assert configure.apply_config(
            epoch, {"coalesce_max_msgs": 24}) is True
        assert get_flag("coalesce_max_msgs") == 24

    def test_weak_hook_unregisters_with_its_owner(self):
        fired = []

        class Owner:
            def hook(self, value):
                fired.append(value)

        owner = Owner()
        register_tunable_hook("coalesce_max_kb", owner.hook)
        configure.apply_tunable("coalesce_max_kb", 2048)
        assert fired == [2048]
        del owner
        gc.collect()
        configure.apply_tunable("coalesce_max_kb", 1024)
        assert fired == [2048]  # dead owner: hook silently pruned

    def test_bad_hook_does_not_block_the_rest(self):
        good = []

        def bad(value):
            raise RuntimeError("boom")

        register_tunable_hook("serving_batch_max_rows", bad)
        register_tunable_hook("serving_batch_max_rows", good.append)
        configure.apply_tunable("serving_batch_max_rows", 512)
        assert good == [512]


# ---------------------------------------------------------------------------
# Broadcast / ack / rejoin through the live runtime


def _config_msg(epoch: int, flags: dict, src=0, dst=0) -> Message:
    import json
    msg = Message(src=src, dst=dst, msg_type=MsgType.Control_Config)
    msg.push(Blob(np.frombuffer(
        json.dumps({"epoch": epoch, "flags": flags}).encode(),
        np.uint8).copy()))
    return msg


class TestConfigBroadcast:
    def test_broadcast_applies_and_acks(self, env):
        zoo = mv.current_zoo()
        controller = zoo._actors[actors.CONTROLLER]
        fired = []
        register_tunable_hook("max_get_staleness", fired.append)
        epoch = _next_epoch()
        zoo.send_to(actors.COMMUNICATOR,
                    _config_msg(epoch, {"max_get_staleness": 12}))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and controller.autotune.acked_epochs().get(0) != epoch:
            time.sleep(0.01)
        assert get_flag("max_get_staleness") == 12
        assert fired == [12]  # the apply hook fired on broadcast
        # The rank's ack reached the controller's convergence view.
        assert controller.autotune.acked_epochs()[0] == epoch

    def test_non_tunable_broadcast_rejected_but_acked(self, env):
        zoo = mv.current_zoo()
        controller = zoo._actors[actors.CONTROLLER]
        before = get_flag("max_get_staleness")
        watermark = configure.applied_config_epoch()
        epoch = _next_epoch(5)
        zoo.send_to(actors.COMMUNICATOR,
                    _config_msg(epoch, {"port": 9999,
                                        "max_get_staleness": 3}))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and 0 not in controller.autotune.acked_epochs():
            time.sleep(0.01)
        # Refused whole: flag untouched, watermark unmoved — and the
        # ack reports the UNCHANGED epoch so the controller can see
        # the rank not converging.
        assert get_flag("max_get_staleness") == before
        assert configure.applied_config_epoch() == watermark
        assert controller.autotune.acked_epochs()[0] == watermark

    def test_stale_broadcast_ignored_on_live_rank(self, env):
        zoo = mv.current_zoo()
        epoch = _next_epoch()
        zoo.send_to(actors.COMMUNICATOR,
                    _config_msg(epoch, {"client_cache_rows": 1024}))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and get_flag("client_cache_rows") != 1024:
            time.sleep(0.01)
        assert get_flag("client_cache_rows") == 1024
        # A reordered older broadcast must not roll the knob back.
        zoo.send_to(actors.COMMUNICATOR,
                    _config_msg(epoch - 1, {"client_cache_rows": 64}))
        time.sleep(0.3)
        assert get_flag("client_cache_rows") == 1024

    def test_rejoining_rank_receives_current_config_epoch(self, env):
        """The rejoin handshake re-anchors a restarted rank: after the
        controller's autotune has moved knobs, a late Control_Register
        (the rejoin path: _node_reply already frozen) must trigger a
        re-broadcast of the cumulative config at the CURRENT epoch."""
        zoo = mv.current_zoo()
        controller = zoo._actors[actors.CONTROLLER]
        mgr = controller.autotune
        # The controller moved a knob at some point in the past.
        mgr._config.update({"max_get_staleness": 7})
        mgr._epoch = _next_epoch(3)
        # A restarted rank re-registers (solo reply path).
        reg = Message(src=0, dst=0,
                      msg_type=MsgType.Control_Register)
        reg.push(Blob(np.array([0, 3, 0], np.int32)))
        controller.receive(reg)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and get_flag("max_get_staleness") != 7:
            time.sleep(0.01)
        assert get_flag("max_get_staleness") == 7
        assert configure.applied_config_epoch() == mgr.epoch
        # ... and the rank acked the re-broadcast epoch.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and mgr.acked_epochs().get(0) != mgr.epoch:
            time.sleep(0.01)
        assert mgr.acked_epochs()[0] == mgr.epoch
        # Drain the solo register reply the rejoin handshake parked in
        # the zoo mailbox, or the shutdown barrier would consume it.
        reply = zoo._pop_control()
        assert reply.type == MsgType.Control_Reply_Register


# ---------------------------------------------------------------------------
# Policies (pure evaluation over synthetic cluster views)


def _mgr(env_zoo) -> AutotuneManager:
    controller = env_zoo._actors[actors.CONTROLLER]
    return AutotuneManager(env_zoo, controller.metrics)


def _view(monitors=None, samples=None) -> dict:
    return {"v": 1, "ranks": {},
            "monitors_sum": monitors or {},
            "samples_merged": samples or {}}


def _gets(count, ms_per=0.5):
    return {"WORKER_PROCESS_GET": {"count": count,
                                   "elapsed_ms": count * ms_per},
            "SERVER_PROCESS_GET": {"count": count,
                                   "elapsed_ms": count * ms_per}}


class TestPolicies:
    def test_staleness_widens_inside_slo(self, env):
        mgr = _mgr(mv.current_zoo())
        # First view: no deltas yet -> every policy holds.
        assert mgr.evaluate(_view(monitors=_gets(1000))) == {}
        # Two consecutive widen verdicts (hysteresis) -> a change.
        assert mgr.evaluate(_view(monitors=_gets(2000))) == {}
        changes = mgr.evaluate(_view(monitors=_gets(3000)))
        assert changes.get("max_get_staleness") == 4
        assert mgr.gauges()["max_get_staleness"]["verdict"] == "up"

    def test_staleness_shrinks_on_slo_violation(self, env):
        set_flag("max_get_staleness", 16)
        mgr = _mgr(mv.current_zoo())
        slo_violating = {"SERVING_LATENCY_MS": {
            "count": 500, "p50": 10.0, "p90": 40.0,
            "p99": float(get_flag("autotune_slo_p99_ms")) * 2,
            "max": 500.0}}
        mgr.evaluate(_view(monitors=_gets(1000)))
        mgr.evaluate(_view(monitors=_gets(2000),
                           samples=slo_violating))
        changes = mgr.evaluate(_view(monitors=_gets(3000),
                                     samples=slo_violating))
        assert changes.get("max_get_staleness") == 8
        assert mgr.gauges()["max_get_staleness"]["verdict"] == "down"

    def test_idle_cluster_judges_nothing(self, env):
        mgr = _mgr(mv.current_zoo())
        for _ in range(4):
            assert mgr.evaluate(_view()) == {}
        assert mgr.gauges()["max_get_staleness"]["verdict"] == "idle"

    def test_hysteresis_needs_consecutive_verdicts(self, env):
        mgr = _mgr(mv.current_zoo())
        mgr.evaluate(_view(monitors=_gets(1000)))
        mgr.evaluate(_view(monitors=_gets(2000)))  # up #1
        assert mgr.evaluate(_view()) == {}          # idle resets
        mgr.evaluate(_view(monitors=_gets(3000)))   # up #1 again
        changes = mgr.evaluate(_view(monitors=_gets(4000)))  # up #2
        assert changes.get("max_get_staleness") == 4

    def test_cooldown_blocks_immediate_restep(self, env):
        mgr = _mgr(mv.current_zoo())
        mgr.evaluate(_view(monitors=_gets(1000)))
        mgr.evaluate(_view(monitors=_gets(2000)))
        assert "max_get_staleness" in \
            mgr.evaluate(_view(monitors=_gets(3000)))
        # Within the cooldown the knob holds even on an up verdict.
        assert mgr.evaluate(_view(monitors=_gets(4000))) == {}

    def test_pinned_knob_never_moves(self, env):
        set_flag("autotune_pin", "max_get_staleness")
        mgr = _mgr(mv.current_zoo())
        for i in range(5):
            assert mgr.evaluate(
                _view(monitors=_gets(1000 * (i + 1)))) == {}
        assert mgr.gauges()["max_get_staleness"]["verdict"] \
            == "pinned"

    def test_unpin_requires_fresh_hysteresis(self, env):
        mgr = _mgr(mv.current_zoo())
        mgr.evaluate(_view(monitors=_gets(1000)))
        mgr.evaluate(_view(monitors=_gets(2000)))  # up vote #1
        set_flag("autotune_pin", "max_get_staleness")
        mgr.evaluate(_view(monitors=_gets(3000)))  # pinned: streak
        set_flag("autotune_pin", "")               # must reset
        # One fresh up verdict must NOT complete the pre-pin streak.
        assert mgr.evaluate(_view(monitors=_gets(4000))) == {}
        changes = mgr.evaluate(_view(monitors=_gets(5000)))
        assert changes.get("max_get_staleness") == 4

    def test_operator_disabled_knob_stays_unmanaged(self, env):
        # -serving_batch_window_ms=0 means "batching disabled"
        # (docs/SERVING.md) — a value OUTSIDE the policy band. The
        # controller must never clamp it back in and re-enable what
        # the operator explicitly turned off.
        set_flag("serving_batch_window_ms", 0.0)
        mgr = _mgr(mv.current_zoo())
        deep = {"DISPATCH_QUEUE_DEPTH[d1]": {
            "count": 500, "p50": 50.0, "p90": 200.0, "p99": 400.0,
            "max": 500.0}}
        for i in range(5):
            changes = mgr.evaluate(
                _view(monitors=_gets(1000 * (i + 1)), samples=deep))
            assert "serving_batch_window_ms" not in changes
        assert get_flag("serving_batch_window_ms") == 0.0
        assert mgr.gauges()["serving_batch_window_ms"]["verdict"] \
            == "unmanaged"

    def test_guardrail_clamps_at_max(self, env):
        set_flag("max_get_staleness",
                 AUTOTUNE_POLICIES["max_get_staleness"]["max"])
        mgr = _mgr(mv.current_zoo())
        for i in range(5):
            changes = mgr.evaluate(
                _view(monitors=_gets(1000 * (i + 1))))
            assert "max_get_staleness" not in changes
        assert get_flag("max_get_staleness") \
            == AUTOTUNE_POLICIES["max_get_staleness"]["max"]

    def test_batch_window_backs_off_when_queues_deep(self, env):
        mgr = _mgr(mv.current_zoo())
        deep = {"DISPATCH_QUEUE_DEPTH[d1]": {
            "count": 500, "p50": 50.0, "p90": 200.0, "p99": 400.0,
            "max": 500.0}}
        mgr.evaluate(_view(monitors=_gets(1000), samples=deep))
        # The depth signal is window-based, not delta-based, so the
        # second consecutive deep view satisfies hysteresis.
        changes = mgr.evaluate(_view(monitors=_gets(2000),
                                     samples=deep))
        assert changes.get("serving_batch_window_ms") == 1.0

    def test_broadcast_refuses_non_tunable(self, env):
        mgr = _mgr(mv.current_zoo())
        with pytest.raises(KeyError):
            mgr._send_config(_next_epoch(), {"port": 1})

    def test_prometheus_gauges(self, env):
        mgr = _mgr(mv.current_zoo())
        mgr.evaluate(_view(monitors=_gets(1000)))
        mgr.note_ack(2, 7)
        text = mgr.prometheus_text()
        assert "mv_autotune_config_epoch" in text
        assert 'mv_autotune_value{knob="max_get_staleness"}' in text
        assert 'mv_autotune_verdict{knob=' in text
        assert 'mv_autotune_rank_epoch{rank="2"} 7' in text


# ---------------------------------------------------------------------------
# Live retune of construction-time caches


class TestLiveRetune:
    def test_row_cache_activates_and_deactivates(self, env):
        from multiverso_tpu.util.dashboard import Dashboard
        table = mv.create_matrix_table(32, 4)
        table.add(np.ones((32, 4), np.float32))
        ids = np.array([1, 2, 3], np.int32)
        gets = Dashboard.get("SERVER_PROCESS_GET")
        before = gets.count
        table.get_rows(ids)
        table.get_rows(ids)
        assert gets.count - before == 2  # inactive: pure pass-through
        configure.apply_tunable("max_get_staleness", 8)
        assert table._row_cache.active
        table.get_rows(ids)  # populates
        before = gets.count
        table.get_rows(ids)
        assert gets.count - before == 0  # served locally
        configure.apply_tunable("max_get_staleness", 0)
        assert not table._row_cache.active
        assert not table._row_cache._rows  # deactivation clears
        before = gets.count
        table.get_rows(ids)
        assert gets.count - before == 1  # back to pass-through

    def test_ryw_holds_across_live_widening(self, env):
        table = mv.create_matrix_table(16, 2)
        configure.apply_tunable("max_get_staleness", 32)
        ids = np.array([3, 5], np.int32)
        for k in range(1, 6):
            table.add_rows(ids, np.ones((2, 2), np.float32))
            got = table.get_rows(ids)
            np.testing.assert_allclose(got, float(k))
        configure.apply_tunable("max_get_staleness", 0)

    def test_activation_edge_ryw_fence(self):
        """The nasty interleaving: a Get reply served BEFORE an own
        add is still in flight when the cache activates; it lands
        after activation carrying the pre-add version. The add's ack
        fence (recorded while the cache was inactive) must keep that
        value from ever serving — read-your-writes across the
        activation edge."""
        from multiverso_tpu.tables.client_cache import (RowCache,
                                                        VersionTracker)
        tracker = VersionTracker()
        cache = RowCache(0, lambda rows: np.zeros(len(rows), np.int64),
                         1, tracker)
        # Inactive: the in-flight own add takes a fence token.
        token = cache.begin_add(np.array([5], np.int64))
        assert token[0] == "fence"
        cache._retune_bound(8)  # live activation (Control_Config)
        # The delayed pre-add reply lands and stores at version 3 ...
        tracker.note(0, 3)
        cache.store(np.array([5]), np.ones((1, 4), np.float32), 3, 0)
        # ... then the add acks at version 4 and the fence fires.
        tracker.note(0, 4)
        cache.finish_add(token)
        out = np.zeros((1, 4), np.float32)
        missing = cache.fetch_into(np.array([5], np.int64), out)
        assert missing.size == 1, \
            "pre-add value served after the acked write (RYW)"

    def test_activation_edge_ryw_fence_over_two_servers(self):
        """The same interleaving with two servers and an Add whose ids
        lie on server 1 alone. This cache was told nothing of how its
        ``server_of`` runs, so its token fences server 0 too: harmless,
        a pre-add row of server 0 is refetched, never served stale;
        and server 1's pre-add row cannot serve either."""
        from multiverso_tpu.tables.client_cache import (RowCache,
                                                        VersionTracker)
        tracker = VersionTracker()
        cache = RowCache(0, lambda rows: np.asarray(rows) // 8, 2,
                         tracker)
        token = cache.begin_add(np.array([9, 13], np.int64))
        assert token == ("fence", [0, 1])
        cache._retune_bound(8)  # live activation (Control_Config)
        # Delayed replies of before the add land on both servers ...
        tracker.note(0, 2)
        tracker.note(1, 3)
        cache.store(np.array([2]), np.ones((1, 4), np.float32), 2, 0)
        cache.store(np.array([9]), np.ones((1, 4), np.float32), 3, 1)
        # ... each server's version moves on, the add acks, the fence
        # fires at what was seen by then.
        tracker.note(0, 3)
        tracker.note(1, 4)
        cache.finish_add(token)
        assert cache._floor_all == {0: 3, 1: 4}
        out = np.zeros((2, 4), np.float32)
        missing = cache.fetch_into(np.array([2, 9], np.int64), out)
        assert missing.tolist() == [2, 9], \
            "a row stored before the fence served after it"
        # A row fetched at or after the fence serves as ever.
        cache.store(np.array([2]), np.ones((1, 4), np.float32), 3, 0)
        assert cache.fetch_into(np.array([2], np.int64),
                                out[:1]).size == 0

    def test_row_cache_capacity_retune_evicts(self, env):
        configure.apply_tunable("max_get_staleness", 8)
        table = mv.create_matrix_table(64, 2)
        table.add(np.ones((64, 2), np.float32))
        table.get_rows(np.arange(32, dtype=np.int32))
        assert len(table._row_cache._rows) == 32
        configure.apply_tunable("client_cache_rows", 8)
        assert len(table._row_cache._rows) <= 8
        configure.apply_tunable("max_get_staleness", 0)

    def test_admission_watermarks_retune_live(self, env):
        from multiverso_tpu.serving.admission import \
            AdmissionController
        ac = AdmissionController()
        assert ac.stats()["max_inflight"] == 64
        configure.apply_tunable("serving_max_inflight", 2)
        configure.apply_tunable("serving_shed_depth", 17)
        assert ac.stats()["max_inflight"] == 2
        assert ac.stats()["shed_depth"] == 17

    def test_worker_coalesce_caps_retune_live(self, env):
        zoo = mv.current_zoo()
        worker = zoo._actors.get(actors.WORKER)
        assert worker._max_batch_msgs == 64
        configure.apply_tunable("coalesce_max_msgs", 16)
        configure.apply_tunable("coalesce_max_kb", 128)
        assert worker._max_batch_msgs == 16
        assert worker._max_batch_bytes == 128 << 10


# ---------------------------------------------------------------------------
# ClusterMetrics ingest hardening


def _report(rank, seq, inc="inc-a", value=1):
    return {"v": 1, "rank": rank, "inc": inc, "seq": seq,
            "monitors": {"X": {"count": value, "elapsed_ms": 0.0}},
            "samples": {}}


class TestIngestHardening:
    def _metrics(self):
        from multiverso_tpu.runtime.metrics import ClusterMetrics
        return ClusterMetrics()

    def test_out_of_order_report_dropped(self):
        cm = self._metrics()
        cm.ingest(_report(1, seq=5, value=50))
        cm.ingest(_report(1, seq=4, value=40))  # late frame: dropped
        cm.ingest(_report(1, seq=5, value=99))  # replay: dropped
        view = cm.cluster_view()
        assert view["monitors_sum"]["X"]["count"] == 50
        assert view["dropped_reports"] == 2

    def test_new_incarnation_resets_the_watermark(self):
        cm = self._metrics()
        cm.ingest(_report(1, seq=9, inc="inc-a", value=90))
        # The rank restarted/rejoined: its reporter starts from seq 1
        # under a fresh incarnation — MUST fold, not drop.
        cm.ingest(_report(1, seq=1, inc="inc-b", value=7))
        view = cm.cluster_view()
        assert view["monitors_sum"]["X"]["count"] == 7
        assert view["dropped_reports"] == 0

    def test_superseded_incarnation_dropped(self):
        # A de-parked PRE-CRASH frame arriving after the restarted
        # rank already reported must not roll the view back to the
        # dead process (or reset the watermark under it).
        cm = self._metrics()
        cm.ingest(_report(1, seq=500, inc="inc-a", value=500))
        cm.ingest(_report(1, seq=1, inc="inc-b", value=1))
        cm.ingest(_report(1, seq=2, inc="inc-b", value=2))
        cm.ingest(_report(1, seq=500, inc="inc-a", value=500))
        view = cm.cluster_view()
        assert view["monitors_sum"]["X"]["count"] == 2
        assert view["dropped_reports"] == 1
        # ... and the live incarnation keeps advancing normally.
        cm.ingest(_report(1, seq=3, inc="inc-b", value=3))
        assert cm.cluster_view()["monitors_sum"]["X"]["count"] == 3

    def test_prior_incarnation_cap_evicts_oldest(self):
        # The cap must evict the OLDEST superseded incarnation: the
        # most recent predecessor's de-parked frames are exactly the
        # ones the guard exists to drop.
        cm = self._metrics()
        n = cm._PRIOR_INC_CAP + 2
        for i in range(n):
            cm.ingest(_report(1, seq=1, inc=f"inc-{i}", value=i))
        cm.ingest(_report(1, seq=999, inc=f"inc-{n - 2}", value=999))
        view = cm.cluster_view()
        assert view["dropped_reports"] == 1
        assert view["monitors_sum"]["X"]["count"] == n - 1

    def test_legacy_reports_without_seq_always_fold(self):
        cm = self._metrics()
        payload = _report(1, seq=None, value=3)
        del payload["seq"], payload["inc"]
        cm.ingest(payload)
        cm.ingest(payload)
        assert cm.cluster_view()["monitors_sum"]["X"]["count"] == 3
        assert cm.cluster_view()["dropped_reports"] == 0

    def test_reporter_stamps_monotonic_seq(self, env):
        zoo = mv.current_zoo()
        from multiverso_tpu.runtime.metrics import MetricsReporter
        reporter = MetricsReporter(zoo)
        controller = zoo._actors[actors.CONTROLLER]
        reporter.flush()
        reporter.flush()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            mark = controller.metrics._report_mark.get(0)
            if mark is not None and mark[1] >= 2:
                break
            time.sleep(0.01)
        mark = controller.metrics._report_mark[0]
        assert mark[0] == reporter._incarnation
        assert mark[1] == 2
