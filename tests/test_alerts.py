"""Fleet telemetry time-series + declarative alerting (ISSUE 17).

Four tiers:

* ``TestTSDB`` — the bounded store itself: scrape-shaped ingestion,
  label selection, the query API, downsampling, and the byte budget
  (the acceptance bound: 10 simulated minutes of 200 series at 1 s
  cadence stays under the configured budget).
* ``TestAlertRules`` / ``TestAlertLifecycle`` — declarative parsing
  with unknown-key rejection, the stable rule-set hash, and the
  pending -> firing -> resolved lifecycle on an injected clock.
* ``TestLabelRoundTrip`` / ``TestMembershipCollect`` /
  ``TestSLOAbsentGauges`` — the satellite regressions: label values
  survive (or are rejected at) the wire format, membership-based
  collection drops departed publishers immediately, and zero-traffic
  SLO windows report ABSENT burn gauges rather than 0.0.
* ``TestConsole`` / ``TestMetricsServerAlerts`` / ``TestSimAlerts`` —
  the consumers: snapshot rendering, the ``/alerts`` + ``/tsdb``
  endpoints, and the sim's alert envelope checked end-to-end.
"""

import json
import math
import os
import time
import urllib.request

import pytest

from tpudist.obs.aggregate import collect, merge_snapshots
from tpudist.obs.alerts import (
    AlertManager,
    AlertRule,
    autoscale_rules,
    default_rules,
    load_rules,
    rules_hash,
)
from tpudist.obs.registry import (
    MetricRegistry,
    split_labels,
    validate_metric_name,
)
from tpudist.obs.tsdb import TSDB, FleetScraper

NS = "alerts-test"

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "console_snapshot.json")


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _tsdb(**kw):
    clock = kw.pop("clock", None) or Clock()
    return TSDB(clock=clock, **kw), clock


# ---------------------------------------------------------------- TSDB


class TestTSDB:
    def test_record_latest_and_window(self):
        db, clk = _tsdb()
        db.record("g", 1.0, t=0.0, kind="gauge")
        db.record("g", 3.0, t=5.0, kind="gauge")
        clk.t = 5.0
        assert db.latest("g") == 3.0
        # a staleness window older than the last point reads absent
        clk.t = 100.0
        assert db.latest("g", window_s=10.0) is None

    def test_delta_and_rate_need_two_points(self):
        db, clk = _tsdb()
        db.record("c", 10.0, t=0.0, kind="counter")
        clk.t = 1.0
        # one point cannot say how fast anything is moving — None, not
        # "the whole cumulative count just happened" (a single scrape
        # of a counter that predates this store must not page)
        assert db.delta("c", 30.0) is None
        assert db.rate("c", 30.0) is None
        db.record("c", 14.0, t=4.0, kind="counter")
        clk.t = 4.0
        assert db.delta("c", 30.0) == pytest.approx(4.0)
        assert db.rate("c", 30.0) == pytest.approx(1.0)

    def test_rate_is_reset_aware(self):
        db, clk = _tsdb()
        for t, v in [(0, 100.0), (1, 110.0), (2, 5.0), (3, 15.0)]:
            db.record("c", v, t=float(t), kind="counter")
        clk.t = 3.0
        # the restart (110 -> 5) contributes its post-reset value to
        # rate(), not a huge negative swing: 10 + 5 + 10 over 3 s
        assert db.rate("c", 10.0) == pytest.approx((10 + 5 + 10) / 3.0)
        # delta() stays plain last-first (gauge semantics)
        assert db.delta("c", 10.0) == pytest.approx(15.0 - 100.0)

    def test_labels_become_series_and_select(self):
        db, clk = _tsdb()
        db.record("q~pool=prefill", 1.0, t=0.0, kind="gauge")
        db.record("q~pool=decode", 9.0, t=0.0, kind="gauge")
        assert {s.name for s in db.select("q")} == \
            {"q~pool=prefill", "q~pool=decode"}
        only = db.select("q", labels={"pool": "decode"})
        assert [s.labels for s in only] == [{"pool": "decode"}]
        assert db.latest("q", labels={"pool": "decode"}) == 9.0

    def test_quantile_and_fold_queries(self):
        db, clk = _tsdb()
        for i in range(10):
            db.record("v", float(i), t=float(i), kind="gauge")
        clk.t = 9.0
        assert db.max_over_time("v", 100.0) == 9.0
        assert db.min_over_time("v", 100.0) == 0.0
        assert db.avg_over_time("v", 100.0) == pytest.approx(4.5)
        assert db.quantile_over_time("v", 0.5, 100.0) in (4.0, 5.0)

    def test_scrape_takes_snapshot_shape(self):
        db, clk = _tsdb()
        snap = {
            "counters": {"router/deaths": {"value": 2.0, "unit": "deaths"}},
            "gauges": {"depth": {"value": 7.0},
                       "absent": {"value": None}},
            "histograms": {"serve/queue_wait_s": {
                "growth": 2.0, "count": 100, "sum": 400.0, "zero": 0,
                "min": 4.0, "max": 4.0, "buckets": {"2": 100}}},
        }
        db.scrape(snap, t=1.0)
        clk.t = 1.0
        assert db.latest("router/deaths") == 2.0
        assert db.latest("depth") == 7.0
        assert db.latest("absent") is None           # None never recorded
        # histograms expand into derived quantile series
        assert db.latest("serve/queue_wait_s/p90") is not None
        assert db.latest("serve/queue_wait_s/count") == 100.0

    def test_downsampling_keeps_older_window_queryable(self):
        db, clk = _tsdb(retention_s=600.0, resolution_s=1.0,
                        downsample_after_s=30.0,
                        downsample_resolution_s=10.0)
        for i in range(120):
            clk.t = float(i)
            db.record("g", float(i), t=clk.t, kind="gauge")
        s = db.select("g")[0]
        assert len(s.coarse) > 0          # old points folded, not dropped
        # a window reaching into the coarse region still answers
        assert db.max_over_time("g", 119.0) == pytest.approx(119.0)
        assert db.min_over_time("g", 119.0) <= 10.0

    def test_byte_budget_bounds_200_series_10_minutes(self):
        # THE acceptance bound: 10 simulated minutes of scraping 200
        # series at 1 s cadence stays under the configured byte budget,
        # enforced by the store itself (downsample + trim), and the
        # store keeps answering queries afterwards.
        budget = 512 * 1024
        db, clk = _tsdb(retention_s=600.0, resolution_s=1.0,
                        downsample_after_s=60.0, byte_budget=budget)
        snap = {"counters": {}, "histograms": {},
                "gauges": {f"g{i}": {"value": 0.0} for i in range(200)}}
        for sec in range(600):
            clk.t = float(sec)
            for g in snap["gauges"].values():
                g["value"] = float(sec)
            db.scrape(snap, t=clk.t)
            assert db.approx_bytes() <= budget, \
                f"budget blown at t={sec}: {db.approx_bytes()}"
        st = db.stats()
        assert st["series"] == 200
        assert st["approx_bytes"] <= budget
        assert st["dropped_points"] > 0          # the bound had teeth
        assert db.latest("g7") == 599.0          # newest data survives

    def test_budget_is_hard_under_cardinality_blowup(self):
        # enough live series that even the 2-point-per-series floor
        # exceeds the budget: whole cold series must be evicted — the
        # cap is hard, not best-effort
        db, clk = _tsdb(byte_budget=16 * 1024)
        snap = {"counters": {}, "histograms": {},
                "gauges": {f"card{i}": {"value": 1.0} for i in range(300)}}
        for sec in range(5):
            clk.t = float(sec)
            db.scrape(snap, t=clk.t)
        assert db.approx_bytes() <= 16 * 1024
        assert 0 < db.stats()["series"] < 300

    def test_to_doc_filters_and_windows(self):
        db, clk = _tsdb()
        db.record("keep/this", 1.0, t=0.0, kind="gauge")
        db.record("drop/that", 1.0, t=0.0, kind="gauge")
        doc = db.to_doc(match="keep")
        assert doc["schema"] == "tpudist.tsdb/1"
        assert list(doc["series"]) == ["keep/this"]


# ------------------------------------------------------------- rules


class TestAlertRules:
    def test_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown keys.*'threshhold'"):
            AlertRule.from_dict({"name": "X", "metric": "m", "op": ">",
                                 "threshhold": 1.0})

    def test_missing_required_rejected(self):
        with pytest.raises(ValueError, match="missing required key"):
            AlertRule.from_dict({"name": "X", "metric": "m", "op": ">"})

    def test_bad_fn_op_severity_rejected(self):
        base = dict(name="X", metric="m", op=">", threshold=1.0)
        with pytest.raises(ValueError, match="unknown fn"):
            AlertRule(**{**base, "fn": "median"})
        with pytest.raises(ValueError, match="unknown op"):
            AlertRule(**{**base, "op": "~"})
        with pytest.raises(ValueError, match="unknown severity"):
            AlertRule(**{**base, "severity": "fatal"})
        with pytest.raises(ValueError, match="needs window_s"):
            AlertRule(**{**base, "fn": "delta"})
        with pytest.raises(ValueError, match="needs q"):
            AlertRule(**{**base, "fn": "quantile_over_time",
                         "window_s": 10.0})

    def test_load_rules_json_and_duplicates(self):
        doc = json.dumps({"rules": [
            {"name": "A", "metric": "m", "op": ">", "threshold": 1},
            {"name": "B", "metric": "m", "op": "<", "threshold": 0},
        ]})
        rules = load_rules(doc)
        assert [r.name for r in rules] == ["A", "B"]
        dup = json.dumps([
            {"name": "A", "metric": "m", "op": ">", "threshold": 1},
            {"name": "A", "metric": "n", "op": ">", "threshold": 2},
        ])
        with pytest.raises(ValueError, match="duplicate alert rule"):
            load_rules(dup)

    def test_rules_hash_stable_order_insensitive_drift_sensitive(self):
        a = AlertRule(name="A", metric="m", op=">", threshold=1.0)
        b = AlertRule(name="B", metric="n", op="<", threshold=0.5)
        assert rules_hash([a, b]) == rules_hash([b, a])
        assert len(rules_hash([a, b])) == 12
        drifted = AlertRule(name="A", metric="m", op=">", threshold=2.0)
        assert rules_hash([a, b]) != rules_hash([drifted, b])

    def test_default_rules_load_and_cover_the_issue_surface(self):
        rules = load_rules(default_rules())
        names = {r.name for r in rules}
        assert {"CoordOutage", "ReplicaLost", "QuarantineActive",
                "SLOBurnHigh", "QueueWaitHigh", "KVHeadroomLow",
                "TierHeadroomLow", "StalePublisher",
                "HandoffFallbackSpike"} <= names
        assert rules_hash(rules) == rules_hash(default_rules())


class TestAlertLifecycle:
    def _mgr(self, rule, **kw):
        clk = Clock()
        db = TSDB(clock=clk)
        return AlertManager(db, [rule], clock=clk, **kw), db, clk

    def test_pending_fires_after_hold_then_resolves(self):
        rule = AlertRule(name="Hot", metric="temp", op=">", threshold=10.0,
                         for_s=2.0)
        mgr, db, clk = self._mgr(rule)
        db.record("temp", 50.0, t=0.0, kind="gauge")
        tr = mgr.evaluate(0.0)
        assert [t["event"] for t in tr] == ["pending"]
        assert not mgr.is_firing("Hot")
        db.record("temp", 50.0, t=1.0, kind="gauge")
        assert mgr.evaluate(1.0) == []               # hold not met yet
        db.record("temp", 50.0, t=2.0, kind="gauge")
        tr = mgr.evaluate(2.0)
        assert [t["event"] for t in tr] == ["firing"]
        assert mgr.is_firing("Hot") and mgr.is_firing()
        assert mgr.fired_names == {"Hot"}
        db.record("temp", 1.0, t=3.0, kind="gauge")
        tr = mgr.evaluate(3.0)
        assert [t["event"] for t in tr] == ["resolved"]
        assert not mgr.is_firing()
        assert mgr.active() == []
        assert len(mgr.resolved) == 1
        assert mgr.fired_names == {"Hot"}            # history survives

    def test_for_s_zero_fires_same_evaluation(self):
        rule = AlertRule(name="Now", metric="x", op=">=", threshold=1.0)
        mgr, db, clk = self._mgr(rule)
        db.record("x", 1.0, t=0.0, kind="gauge")
        events = [t["event"] for t in mgr.evaluate(0.0)]
        assert events == ["pending", "firing"]

    def test_pending_blip_never_counts_as_fired(self):
        rule = AlertRule(name="Hold", metric="x", op=">", threshold=0.0,
                         for_s=10.0)
        mgr, db, clk = self._mgr(rule)
        db.record("x", 5.0, t=0.0, kind="gauge")
        mgr.evaluate(0.0)
        db.record("x", -1.0, t=1.0, kind="gauge")
        mgr.evaluate(1.0)
        assert mgr.fired_names == set()
        assert len(mgr.resolved) == 0     # pending-only blips don't resolve

    def test_absent_and_nan_never_breach(self):
        rule = AlertRule(name="X", metric="missing", op="<", threshold=5.0)
        mgr, db, clk = self._mgr(rule)
        assert mgr.evaluate(0.0) == []               # no series at all
        db.record("missing", float("nan"), t=1.0, kind="gauge")
        assert mgr.evaluate(1.0) == []               # NaN compares False
        assert not mgr.fired_names

    def test_lifecycle_counters_when_registry_given(self):
        reg = MetricRegistry()
        clk = Clock()
        db = TSDB(clock=clk)
        rule = AlertRule(name="R", metric="x", op=">", threshold=0.0)
        mgr = AlertManager(db, [rule], registry=reg, clock=clk)
        db.record("x", 1.0, t=0.0, kind="gauge")
        mgr.evaluate(0.0)
        snap = reg.snapshot()
        assert snap["counters"]["alerts/fired"]["value"] == 1
        assert snap["gauges"]["alerts/firing"]["value"] == 1.0
        db.record("x", -1.0, t=1.0, kind="gauge")
        mgr.evaluate(1.0)
        snap = reg.snapshot()
        assert snap["counters"]["alerts/resolved"]["value"] == 1
        assert snap["gauges"]["alerts/firing"]["value"] == 0.0

    def test_to_doc_shape(self):
        rule = AlertRule(name="R", metric="x", op=">", threshold=0.0)
        mgr, db, clk = self._mgr(rule)
        db.record("x", 1.0, t=0.0, kind="gauge")
        mgr.evaluate(0.0)
        doc = mgr.to_doc()
        assert doc["schema"] == "tpudist.alerts/1"
        assert doc["rules_hash"] == mgr.rules_hash
        assert doc["fired_ever"] == ["R"]
        assert doc["active"][0]["state"] == "firing"
        json.dumps(doc)                              # wire-serializable

    def test_autoscale_rules_mirror_config(self):
        class Cfg:
            target_wait_s = 0.5
            max_burn_rate = 4.0
            min_kv_free_frac = None
            min_tier_headroom_frac = 0.2
        names = [r.name for r in autoscale_rules(Cfg())]
        assert names == ["AutoscaleQueueWait", "AutoscaleBurnRate",
                         "AutoscaleTierPressure"]


# ---------------------------------------------------- label round-trip


class TestLabelRoundTrip:
    def test_slash_in_value_roundtrips(self):
        # '/' is legal in label values and must survive the full path:
        # registry name -> snapshot -> merge -> TSDB labels
        name = "serve/latency~route=/v1/chat"
        validate_metric_name(name)                   # accepted
        base, labels = split_labels(name)
        assert (base, labels) == ("serve/latency", {"route": "/v1/chat"})
        reg = MetricRegistry()
        reg.gauge(name).set(1.0)
        merged = merge_snapshots({0: {**reg.snapshot(), "rank": 0}})
        assert name in merged["gauges"]
        db = TSDB(clock=Clock())
        db.scrape(merged, t=0.0)
        assert db.select(base, labels={"route": "/v1/chat"})

    def test_equals_in_value_rejected_at_registration(self):
        # 'a=b' as a value would silently mis-split on read — the
        # registry must reject it at metric creation, not corrupt later
        reg = MetricRegistry()
        with pytest.raises(ValueError, match="cannot round-trip"):
            reg.counter("hits~tenant=a=b")
        with pytest.raises(ValueError, match="cannot round-trip"):
            validate_metric_name('hits~tenant=say"hi"')

    def test_bare_tilde_part_rejected_on_write_lenient_on_read(self):
        with pytest.raises(ValueError, match="not key=value"):
            validate_metric_name("name~notatag")
        # the read path folds it back instead of dropping data
        assert split_labels("name~notatag") == ("name~notatag", {})

    def test_prometheus_export_escapes_and_labels_histograms(self):
        from tpudist.obs.export import to_prometheus

        reg = MetricRegistry()
        reg.gauge("depth~pool=decode").set(3.0)
        h = reg.histogram("wait~pool=decode", unit="s")
        h.record(0.5)
        text = to_prometheus(reg.snapshot())
        assert 'depth{pool="decode"} 3' in text
        # histogram series carry the split labels AND the le bucket tag
        assert 'wait_bucket{' in text
        assert 'pool="decode"' in text
        assert 'wait_count{pool="decode"}' in text

    def test_prometheus_label_value_escaping(self):
        # banned chars can't enter via the registry, but merged docs
        # from older publishers can carry anything — the exporter must
        # escape quotes per the exposition format rather than emit a
        # syntactically broken sample
        from tpudist.obs.export import to_prometheus

        snap = {"gauges": {'g~note=a"b': {"value": 1.0}},
                "counters": {}, "histograms": {}}
        assert 'note="a\\"b"' in to_prometheus(snap)


# ------------------------------------------------- membership cutoff


class FakeCoord:
    def __init__(self):
        self.kv: dict[str, bytes] = {}
        self.live_set: set[str] = set()

    def keys(self, prefix=""):
        return [k for k in list(self.kv) if k.startswith(prefix)]

    def get(self, key):
        return self.kv.get(key)

    def set(self, key, value):
        self.kv[key] = value

    def delete(self, key):
        self.kv.pop(key, None)

    def live(self):
        return set(self.live_set)


def _register(fc, rid, rank):
    fc.kv[f"{NS}/replica/{rid}"] = json.dumps(
        {"replica_id": rid, "rank": rank}).encode()
    fc.live_set.add(f"{NS}:{rid}")


def _publish(fc, rank, *, wait_idx=None, published_at=None):
    snap = {"rank": rank,
            "published_at": published_at if published_at is not None
            else time.time(),
            "gauges": {}, "counters": {}, "histograms": {}}
    if wait_idx is not None:
        v = float(2.0 ** wait_idx)
        snap["histograms"]["serve/queue_wait_s"] = {
            "growth": 2.0, "count": 100, "sum": v * 100, "zero": 0,
            "min": v, "max": v, "buckets": {str(wait_idx): 100}}
    fc.kv[f"{NS}/metrics/{rank}"] = json.dumps(snap).encode()


class TestMembershipCollect:
    def test_members_cutoff_drops_departed_rank(self):
        fc = FakeCoord()
        _publish(fc, 0, wait_idx=0)
        _publish(fc, 1, wait_idx=6)
        both = collect(fc, f"{NS}/metrics")
        assert set(both) == {0, 1}
        # rank 1 left the fleet: a FRESH snapshot is still dropped —
        # membership beats age
        only = collect(fc, f"{NS}/metrics", members={0})
        assert set(only) == {0}
        # None = no membership info, NOT "no members"
        assert set(collect(fc, f"{NS}/metrics", members=None)) == {0, 1}

    def test_scraper_reads_members_from_registrations(self):
        fc = FakeCoord()
        _register(fc, "r0", 0)
        _publish(fc, 0, wait_idx=0)
        _publish(fc, 7, wait_idx=6)       # departed publisher, fresh stamp
        clk = Clock()
        db = TSDB(clock=clk)
        scraper = FleetScraper(db, client=fc, namespace=NS, clock=clk)
        assert scraper.members() == {0}
        out = scraper.tick(0.0)
        assert out["coord_up"] is True
        assert out["publishers"] == 1
        assert db.latest("fleet/replicas_publishing", at=0.0) == 1.0
        # the departed rank's pinned histogram stayed OUT of the merge
        assert db.latest("serve/queue_wait_s/p90", at=0.0) == \
            pytest.approx(1.0, rel=0.5)

    def test_autoscaler_ignores_deregistered_ranks_fresh_metrics(self):
        # the satellite regression: a departed replica keeps publishing
        # (or its last window is still fresh) — the autoscaler's merged
        # wait quantile must not read it once the registration is gone
        from tpudist.runtime.autoscaler import AutoscaleConfig, Autoscaler

        fc = FakeCoord()
        _register(fc, "r0", 0)
        _register(fc, "r1", 1)
        _publish(fc, 0, wait_idx=0)       # 1 s waits
        _publish(fc, 1, wait_idx=6)       # 64 s waits
        clk = Clock(100.0)
        cfg = AutoscaleConfig(min_replicas=1, max_replicas=4,
                              target_wait_s=10.0, low_wait_s=0.1,
                              breach_polls=1, poll_s=0.5,
                              max_metric_age_s=1e9)
        sc = Autoscaler(fc, namespace=NS, config=cfg, clock=clk,
                        spawner=lambda n: [])
        sc.poll()
        assert sc.decision_log[-1]["wait_q"] > 10.0   # both ranks merged
        fc.delete(f"{NS}/replica/r1")                  # r1 leaves
        fc.live_set.discard(f"{NS}:r1")
        _publish(fc, 1, wait_idx=6)                    # still publishing!
        clk.t += 1.0
        sc.poll()
        assert sc.decision_log[-1]["wait_q"] < 10.0    # r1 dropped

    def test_scraper_coord_outage_is_a_signal(self):
        class DownCoord(FakeCoord):
            def keys(self, prefix=""):
                raise ConnectionError("coord down")

        clk = Clock()
        db = TSDB(clock=clk)
        mgr = AlertManager(db, default_rules(), clock=clk)
        scraper = FleetScraper(db, client=DownCoord(), namespace=NS,
                               alerts=mgr, clock=clk)
        for t in (0.0, 1.0, 2.0):
            out = scraper.tick(t)
            assert out["coord_up"] is False
        assert db.latest("fleet/coord_up", at=2.0) == 0.0
        assert "CoordOutage" in mgr.fired_names


# ------------------------------------------------ SLO absent gauges


class TestSLOAbsentGauges:
    def test_zero_traffic_window_reports_absent_not_zero(self):
        from tpudist.obs.events import SLOTracker

        reg = MetricRegistry()
        slo = SLOTracker(registry=reg, windows=(60.0,))
        snap = reg.snapshot()
        # no traffic ever: the gauge exists but is ABSENT (null on the
        # wire), so dashboards show "no data", not a healthy-looking 0.0
        assert snap["gauges"]["slo/burn_rate_60s"]["value"] is None
        slo.observe(good=False)
        val = reg.snapshot()["gauges"]["slo/burn_rate_60s"]["value"]
        assert val is not None and val > 0.0
        slo.clear()
        assert reg.snapshot()["gauges"]["slo/burn_rate_60s"]["value"] is None

    def test_absent_burn_gauge_never_recorded_by_tsdb(self):
        from tpudist.obs.events import SLOTracker

        reg = MetricRegistry()
        SLOTracker(registry=reg, windows=(60.0,))
        db = TSDB(clock=Clock())
        db.scrape(reg.snapshot(), t=0.0)
        assert db.select("slo/burn_rate_60s") == []

    def test_burn_rates_method_still_returns_zero_for_empty(self):
        # burn_rates() (the sim summary + autoscaler path) keeps its
        # 0.0-for-empty contract; only the GAUGES go absent
        from tpudist.obs.events import SLOTracker

        slo = SLOTracker(registry=MetricRegistry(), windows=(60.0,))
        assert slo.burn_rates()[60.0] == 0.0


# ----------------------------------------------------------- console


class TestConsole:
    def test_sparkline_handles_empty_and_nan(self):
        from tpudist.obs.console import sparkline

        assert sparkline([]) == ""
        assert sparkline([float("nan")]) == ""
        line = sparkline([0.0, float("nan"), 1.0])
        assert len(line) == 2

    def test_render_is_pure_and_covers_sections(self):
        from tpudist.obs.console import CONSOLE_SCHEMA, render

        doc = {"schema": CONSOLE_SCHEMA, "namespace": "ns",
               "generated_at": 0.0,
               "replicas": {"r0": {"rank": 0, "role": "both",
                                   "live": True, "draining": False,
                                   "quarantined": False}},
               "merged": {},
               "tsdb": {"stats": {"series": 1, "approx_bytes": 100,
                                  "byte_budget": 1000},
                        "series": {"serve/queue_depth": {
                            "points": [[0.0, 1.0], [1.0, 2.0]]}}},
               "alerts": {"rules_hash": "abc", "fired_ever": ["X"],
                          "active": [{"rule": "X", "state": "firing",
                                      "severity": "page", "value": 3.0}]},
               "events": [{"t": 0.0, "kind": "done", "i": 4,
                           "trace": "t-1"}]}
        frame = render(doc)
        assert frame == render(doc)       # pure
        assert "REPLICAS" in frame and "r0" in frame
        assert "[PAGE] X" in frame
        assert "fired this session: X" in frame
        assert "serve/queue_depth" in frame
        assert "done" in frame and "req=4" in frame

    def test_main_once_renders_checked_in_fixture(self, capsys):
        from tpudist.obs.console import main

        assert os.path.exists(FIXTURE), "console fixture missing"
        assert main(["--once", "--snapshot", FIXTURE]) == 0
        out = capsys.readouterr().out
        assert "ALERTS" in out and "SERIES" in out

    def test_main_rejects_wrong_schema(self, tmp_path, capsys):
        from tpudist.obs.console import main

        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema": "nope/1"}))
        assert main(["--once", "--snapshot", str(bad)]) == 2


# ---------------------------------------------------- HTTP endpoints


class TestMetricsServerAlerts:
    def test_alerts_and_tsdb_endpoints(self):
        from tpudist.obs.export import MetricsServer

        reg = MetricRegistry()
        reg.counter("hits").inc()
        clk = Clock()
        db = TSDB(clock=clk)
        db.record("serve/queue_depth", 2.0, t=0.0, kind="gauge")
        db.record("other/series", 1.0, t=0.0, kind="gauge")
        rule = AlertRule(name="R", metric="serve/queue_depth", op=">",
                         threshold=1.0)
        mgr = AlertManager(db, [rule], clock=clk)
        mgr.evaluate(0.0)
        srv = MetricsServer(reg, alerts=mgr, tsdb=db)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            alerts = json.loads(urllib.request.urlopen(
                f"{base}/alerts", timeout=5).read())
            assert alerts["schema"] == "tpudist.alerts/1"
            assert alerts["fired_ever"] == ["R"]
            tsdb_doc = json.loads(urllib.request.urlopen(
                f"{base}/tsdb?match=queue", timeout=5).read())
            assert tsdb_doc["schema"] == "tpudist.tsdb/1"
            assert list(tsdb_doc["series"]) == ["serve/queue_depth"]
            # the 404 body advertises the new endpoints
            try:
                urllib.request.urlopen(f"{base}/nope", timeout=5)
            except urllib.error.HTTPError as e:
                listing = json.loads(e.read())
                assert "/alerts" in listing["paths"]
                assert "/tsdb" in listing["paths"]
        finally:
            srv.close()


# ------------------------------------------------------ sim envelope


class TestSimAlerts:
    def test_alert_envelope_parses_and_checks(self):
        from tpudist.sim.scenario import ScenarioSpec

        spec = ScenarioSpec.from_dict({
            "name": "t", "duration_s": 1.0,
            "arrival": {"kind": "constant", "rate": 1.0},
            "envelope": {"alerts": {"must_fire": ["CoordOutage"],
                                    "must_not_fire": "*"}}})
        row = {"scenario": "t", "alerts_fired": ["CoordOutage"]}
        assert spec.envelope.check(row) == []
        bad = spec.envelope.check({"scenario": "t",
                                   "alerts_fired": ["ReplicaLost"]})
        assert any("CoordOutage" in v for v in bad)       # must_fire miss
        assert any("ReplicaLost" in v for v in bad)       # stranger fired
        missing = spec.envelope.check({"scenario": "t"})
        assert any("alerts_fired" in v for v in missing)

    def test_alert_envelope_unknown_key_rejected(self):
        from tpudist.sim.scenario import ScenarioSpec

        with pytest.raises(ValueError, match="unknown keys.*'must_page'"):
            ScenarioSpec.from_dict({
                "name": "t", "duration_s": 1.0,
                "arrival": {"kind": "constant", "rate": 1.0},
                "envelope": {"alerts": {"must_page": ["X"]}}})

    def test_steady_state_fires_nothing_end_to_end(self):
        # the zero-false-positive acceptance gate, runnable offline:
        # the REAL scrape -> TSDB -> rule path on the virtual clock
        from tpudist.sim.scenario import builtin
        from tpudist.sim.simulator import FleetSim

        sim = FleetSim(builtin("steady_state"))
        row = sim.run()
        assert row["alerts_fired"] == []
        assert row["envelope_ok"] is True, row["violations"]
        assert sim.scraper.ticks > 10            # the plane actually ran
        assert row["alert_rules_hash"] == rules_hash(default_rules())

    def test_coord_brownout_fires_exactly_coord_outage(self):
        from tpudist.sim.scenario import builtin
        from tpudist.sim.simulator import FleetSim

        row = FleetSim(builtin("coord_brownout")).run()
        assert row["alerts_fired"] == ["CoordOutage"]
        assert row["envelope_ok"] is True, row["violations"]

    @pytest.mark.parametrize("leftover", ["queue_wait", "degraded",
                                          "kv_free"])
    def test_a_process_leftover_fires_nothing_in_a_brownout(
            self, leftover, monkeypatch):
        """While the coord is down the scraper reads the process's registry
        alone: replica state another test left there (a ``ServeLoop``'s
        wait window, a gauge) is no state of the simulated fleet."""
        from tpudist import obs
        from tpudist.sim.scenario import builtin
        from tpudist.sim.simulator import FleetSim

        name = {"queue_wait": "serve/queue_wait_s",
                "degraded": "serve/degraded",
                "kv_free": "fleet/kv_free_frac"}[leftover]
        # the leftover is made in a copy of the registry's table, so that
        # it is no leftover for the tests after this one
        monkeypatch.setattr(obs.registry, "_metrics", {
            k: m for k, m in obs.registry.metrics().items() if k != name})
        if leftover == "queue_wait":
            waits = obs.histogram(name, unit="s", window_s=60.0)
            for _ in range(20):
                waits.record(3.0)
        else:
            obs.gauge(name).set(1.0 if leftover == "degraded" else 0.01)
        row = FleetSim(builtin("coord_brownout")).run()
        assert row["alerts_fired"] == ["CoordOutage"]
        assert row["envelope_ok"] is True, row["violations"]
