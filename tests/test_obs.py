"""tpudist.obs — registry math, lazy accumulation, spans, cross-host
aggregation through the coord store, and exporter round-trips.

The acceptance contract under test (ISSUE 1): recording never syncs (the
MetricLogger discipline), merged cluster views equal the sum of per-worker
counters, and merged histogram quantiles are EXACT for a known
power-of-growth input distribution."""

import json
import math
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import obs
from tpudist.obs.registry import hist_quantile, summarize


def _registry():
    return obs.MetricRegistry()


# -- histogram bucket / quantile math ---------------------------------------

class TestHistogramMath:
    def test_bucket_indices_are_log_floor(self):
        r = _registry()
        h = r.histogram("h")
        # growth 2: [1,2) -> 0, [2,4) -> 1, [4,8) -> 2, ...
        for v in (1.0, 1.5, 2.0, 3.9, 4.0, 7.9, 1024.0):
            h.record(v)
        snap = r.snapshot()["histograms"]["h"]
        assert snap["buckets"] == {"0": 2, "1": 2, "2": 2, "10": 1}
        assert snap["count"] == 7 and snap["zero"] == 0

    def test_exact_power_boundaries_no_float_drift(self):
        # log(2**k)/log(2) lands exactly on k for every k that matters
        r = _registry()
        h = r.histogram("h")
        for k in range(-20, 64):
            h.record(2.0 ** k)
        snap = r.snapshot()["histograms"]["h"]
        assert snap["buckets"] == {str(k): 1 for k in range(-20, 64)}

    def test_zero_and_negative_go_to_zero_bucket(self):
        r = _registry()
        h = r.histogram("h")
        for v in (0.0, -3.0, 5.0):
            h.record(v)
        snap = r.snapshot()["histograms"]["h"]
        assert snap["zero"] == 2 and snap["count"] == 3
        assert snap["min"] == -3.0 and snap["max"] == 5.0

    def test_quantiles_exact_for_power_of_two_inputs(self):
        # 100 observations: 50x1, 40x8, 10x64 — every value sits on a
        # bucket lower bound, so nearest-rank quantiles are EXACT
        r = _registry()
        h = r.histogram("lat", unit="s")
        h.record([1.0] * 50 + [8.0] * 40 + [64.0] * 10)
        s = h.summary()
        assert s["count"] == 100
        assert s["p50"] == 1.0      # rank 50 is the last 1.0
        assert s["p90"] == 8.0      # rank 90 is the last 8.0
        assert s["p99"] == 64.0
        assert s["mean"] == pytest.approx((50 + 320 + 640) / 100)

    def test_quantile_edge_cases(self):
        assert math.isnan(hist_quantile(
            {"count": 0, "growth": 2.0, "buckets": {}, "zero": 0,
             "sum": 0.0, "min": None, "max": None}, 0.5))
        r = _registry()
        h = r.histogram("h")
        h.record(0.0)
        h.record(4.0)
        snap = r.snapshot()["histograms"]["h"]
        assert hist_quantile(snap, 0.5) == 0.0   # zero bucket holds rank 1
        assert hist_quantile(snap, 1.0) == 4.0

    def test_custom_growth(self):
        r = _registry()
        h = r.histogram("h", growth=10.0)
        for v in (1.0, 10.0, 100.0, 5.0):
            h.record(v)
        snap = r.snapshot()["histograms"]["h"]
        assert snap["buckets"] == {"0": 2, "1": 1, "2": 1}
        with pytest.raises(ValueError, match="growth"):
            r.histogram("bad", growth=1.0)

    def test_kind_collision_raises(self):
        r = _registry()
        r.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("x")
        assert r.counter("x") is r.counter("x")  # same-kind lookup is fine


# -- lazy accumulation (the no-sync-per-record contract) --------------------

class TestLazyAccumulation:
    def test_no_device_get_until_snapshot(self, monkeypatch):
        r = _registry()
        c = r.counter("steps")
        h = r.histogram("loss_h")
        g = r.gauge("loss")
        calls = {"n": 0}
        real = jax.device_get

        def counting(x):
            calls["n"] += 1
            return real(x)

        monkeypatch.setattr(jax, "device_get", counting)
        for i in range(20):
            v = jnp.float32(2.0 ** (i % 4))   # device scalars
            c.inc(jnp.int32(1))
            h.record(v)
            g.set(v)
        assert calls["n"] == 0                # recording never synced
        snap = r.snapshot()
        assert calls["n"] == 1                # ONE batched sync for all
        assert snap["counters"]["steps"]["value"] == 20
        assert snap["histograms"]["loss_h"]["count"] == 20
        assert snap["gauges"]["loss"]["value"] == 8.0

    def test_pending_holds_raw_device_arrays(self):
        r = _registry()
        h = r.histogram("h")
        v = jnp.float32(4.0)
        h.record(v)
        assert h._pending[0] is v             # unconverted, unfetched
        assert h._gens[-1]["count"] == 0      # nothing folded yet

    def test_plain_python_values_skip_jax_entirely(self, monkeypatch):
        r = _registry()
        r.counter("c").inc(3)
        r.histogram("h").record(2.0)
        monkeypatch.setattr(jax, "device_get",
                            lambda x: pytest.fail("jax sync on host data"))
        snap = r.snapshot()
        assert snap["counters"]["c"]["value"] == 3

    def test_stacked_array_counts_every_element(self):
        # the fused train loop records [n]-step metric stacks
        r = _registry()
        h = r.histogram("h")
        h.record(jnp.asarray([1.0, 2.0, 4.0, 8.0]))
        snap = r.snapshot()["histograms"]["h"]
        assert snap["count"] == 4
        assert snap["buckets"] == {"0": 1, "1": 1, "2": 1, "3": 1}
        g = r.gauge("g")
        g.set(jnp.asarray([1.0, 7.0]))        # gauge folds to last element
        assert g.value() == 7.0


# -- spans ------------------------------------------------------------------

class TestSpans:
    def test_nesting_depths_and_order(self):
        t = obs.SpanTracer()
        with t.span("outer"):
            with t.span("inner", step=3):
                pass
            with t.span("inner2"):
                pass
        names = [(e["name"], e["args"]["depth"]) for e in t.events()]
        # completion order: children close before the parent
        assert names == [("inner", 1), ("inner2", 1), ("outer", 0)]
        inner, inner2, outer = t.events()
        assert inner["args"]["step"] == 3
        assert outer["dur"] >= inner["dur"] + inner2["dur"]

    def test_chrome_trace_json_validity(self, tmp_path):
        t = obs.SpanTracer()
        with t.span("a"):
            with t.span("b"):
                pass
        path = t.write(str(tmp_path / "trace.json"))
        doc = json.loads((tmp_path / "trace.json").read_text())
        assert path.endswith("trace.json")
        assert doc["displayTimeUnit"] == "ms"
        for e in doc["traceEvents"]:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)

    def test_exception_still_records_and_pops(self):
        t = obs.SpanTracer()
        with pytest.raises(RuntimeError):
            with t.span("will_raise"):
                raise RuntimeError("boom")
        assert [e["name"] for e in t.events()] == ["will_raise"]
        with t.span("after"):
            pass
        assert t.events()[-1]["args"]["depth"] == 0  # stack popped cleanly

    def test_max_events_drops_not_grows(self):
        t = obs.SpanTracer(max_events=2)
        for _ in range(5):
            with t.span("s"):
                pass
        assert len(t.events()) == 2 and t.dropped == 3
        t.clear()
        assert t.events() == [] and t.dropped == 0

    def test_fence_flag_runs_effects_barrier(self):
        t = obs.SpanTracer(fence=True)
        with t.span("fenced"):
            jnp.zeros(4) + 1    # dispatch something; barrier must not raise
        assert t.events()[0]["name"] == "fenced"


# -- cross-host aggregation through the coord store -------------------------

def _coord_pair():
    try:
        from tpudist.runtime.coord import CoordClient, CoordServer

        server = CoordServer(0)
    except Exception as e:  # NativeUnavailable or build failure
        pytest.skip(f"native coord store unavailable: {e}")
    return server, CoordClient("127.0.0.1", server.port)


class TestAggregation:
    def test_two_worker_merge_sums_and_exact_quantiles(self):
        server, client = _coord_pair()
        try:
            # two simulated workers, each its own registry + publisher
            regs = [obs.MetricRegistry() for _ in range(2)]
            for rank, (reg, steps) in enumerate(zip(regs, (30, 12))):
                reg.counter("train/steps").inc(steps)
                reg.gauge("queue").set(rank + 1)
            # known distribution split across workers: the merged
            # histogram must report EXACT quantiles (all powers of 2)
            regs[0].histogram("lat", unit="s").record([1.0] * 50)
            regs[1].histogram("lat", unit="s").record(
                [8.0] * 40 + [64.0] * 10)
            pubs = [obs.MetricsPublisher(client, rank, reg)
                    for rank, reg in enumerate(regs)]
            for p in pubs:
                p.publish()
            merged = obs.collect_and_merge(client)
            assert merged["workers"] == [0, 1]
            assert merged["counters"]["train/steps"]["value"] == 42
            assert merged["counters"]["train/steps"]["per_worker"] == {
                "0": 30.0, "1": 12.0}
            assert merged["gauges"]["queue"]["value"] == 3
            lat = merged["histograms"]["lat"]
            assert lat["count"] == 100
            assert lat["per_worker"] == {"0": 50, "1": 50}
            s = summarize(lat)
            assert s["p50"] == 1.0 and s["p90"] == 8.0 and s["p99"] == 64.0
        finally:
            client.close()
            server.stop()

    def test_publisher_background_thread_and_restart_overwrite(self):
        server, client = _coord_pair()
        try:
            reg = obs.MetricRegistry()
            reg.counter("c").inc(1)
            pub = obs.MetricsPublisher(client, 0, reg, interval_s=0.05)
            pub.start()
            import time as _t

            deadline = _t.monotonic() + 5.0
            while not obs.collect(client) and _t.monotonic() < deadline:
                _t.sleep(0.02)
            pub.stop()
            assert obs.collect(client)[0]["counters"]["c"]["value"] == 1
            # a restarted worker's publish REPLACES its old snapshot
            reg2 = obs.MetricRegistry()
            reg2.counter("c").inc(7)
            obs.MetricsPublisher(client, 0, reg2).publish()
            merged = obs.collect_and_merge(client)
            assert merged["counters"]["c"]["value"] == 7
        finally:
            client.close()
            server.stop()

    def test_growth_mismatch_refuses_merge(self):
        a = obs.MetricRegistry()
        b = obs.MetricRegistry()
        a.histogram("h", growth=2.0).record(1.0)
        b.histogram("h", growth=10.0).record(1.0)
        snaps = {0: a.snapshot(), 1: b.snapshot()}
        with pytest.raises(ValueError, match="growth"):
            obs.merge_snapshots(snaps)


# -- exporters --------------------------------------------------------------

class TestExporters:
    def test_jsonl_bench_schema_and_key_order(self):
        line = obs.jsonl_line("tok_per_s", 123.4, "tok/s", 1.07, mfu=0.31)
        obj = json.loads(line)
        assert list(obj) == ["metric", "value", "unit", "vs_baseline", "mfu"]
        assert obj["value"] == 123.4 and obj["vs_baseline"] == 1.07

    def test_snapshot_to_jsonl_parses_line_by_line(self):
        r = _registry()
        r.counter("steps", unit="steps").inc(5)
        r.gauge("loss").set(0.25)
        r.histogram("lat", unit="s").record([1.0, 2.0, 4.0])
        lines = obs.snapshot_to_jsonl(r.snapshot())
        assert len(lines) == 2 + 7            # 7 stats per histogram
        parsed = [json.loads(ln) for ln in lines]
        for obj in parsed:
            assert set(obj) >= {"metric", "value", "unit", "vs_baseline"}
        by_name = {o["metric"]: o["value"] for o in parsed}
        assert by_name["steps"] == 5
        assert by_name["lat/p50"] == 2.0
        assert by_name["lat/count"] == 3

    def test_prometheus_text_round_trip(self):
        r = _registry()
        r.counter("train/steps", unit="steps").inc(42)
        r.gauge("queue_depth").set(3)
        h = r.histogram("lat", unit="s")
        h.record([1.0] * 2 + [4.0] * 3 + [0.0])
        text = obs.to_prometheus(r.snapshot())
        lines = [ln for ln in text.splitlines() if ln]
        assert "# TYPE train_steps counter" in lines   # '/' sanitized
        metrics = {}
        for ln in lines:
            if ln.startswith("#"):
                continue
            key, val = ln.rsplit(" ", 1)
            metrics[key] = float(val)
        assert metrics["train_steps"] == 42
        assert metrics["queue_depth"] == 3
        # cumulative le buckets: upper edges growth**(idx+1); the zero
        # observation folds into the smallest edge
        assert metrics['lat_bucket{le="2.0"}'] == 3    # 0.0 + two 1.0s
        assert metrics['lat_bucket{le="8.0"}'] == 6
        assert metrics['lat_bucket{le="+Inf"}'] == 6
        assert metrics["lat_count"] == 6
        assert metrics["lat_sum"] == pytest.approx(14.0)

    def test_http_metrics_endpoint(self):
        r = _registry()
        r.counter("hits").inc(9)
        srv = obs.MetricsServer(registry=r)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "hits 9.0" in text
            doc = json.loads(
                urllib.request.urlopen(base + "/metrics.json").read())
            assert doc["counters"]["hits"]["value"] == 9
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope")
        finally:
            srv.close()

    def test_metrics_server_arg_validation(self):
        with pytest.raises(ValueError, match="exactly one"):
            obs.MetricsServer()


# -- instrumented consumers report through the global registry --------------

class TestGlobalRegistryWiring:
    def test_module_level_conveniences_share_one_registry(self):
        c = obs.counter("test_obs/once")
        c.inc(2)
        assert obs.registry.counter("test_obs/once").value() == 2

    def test_serving_records_without_hot_loop_syncs(self):
        from tpudist.models.serving import Request, ServeLoop
        from tpudist.models.transformer import TransformerConfig, TransformerLM

        cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, embed_dim=64, max_seq_len=96)
        model = TransformerLM(cfg)
        params = model.init(jax.random.key(0),
                            np.zeros((1, 8), np.int32))["params"]
        loop = ServeLoop(cfg, params, num_slots=2, steps_per_sync=5,
                         decode_attention="dense", prefill_chunk=8)
        req0 = loop._obs_requests.value()
        done = loop.run([Request(np.arange(1, 5, dtype=np.int32), 6, rid=i)
                         for i in range(3)])
        assert len(done) == 3
        assert loop._obs_requests.value() - req0 == 3
        snap = obs.snapshot()
        lat = snap["histograms"]["serve/request_latency"]
        assert lat["count"] >= 3
        assert snap["gauges"]["serve/queue_depth"]["value"] == 0

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_serving_counts_the_decode_kernels_rows(self, layout):
        """``serve/decode_rows_computed`` and ``serve/decode_rows_live``
        are registered by every loop; the paged layout ticks them (live
        rows never more than computed ones), the dense layout leaves
        them where they were."""
        from tpudist.models.serving import Request, ServeLoop
        from tpudist.models.transformer import TransformerConfig, TransformerLM

        cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, embed_dim=64, max_seq_len=96)
        params = TransformerLM(cfg).init(
            jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
        kw = (dict(decode_attention="dense") if layout == "dense" else
              dict(cache_layout="paged", kv_block_size=16))
        loop = ServeLoop(cfg, params, num_slots=2, steps_per_sync=5,
                         prefill_chunk=8, **kw)
        names = ("serve/decode_rows_computed", "serve/decode_rows_live")
        before = [obs.counter(n).value() for n in names]
        loop.run([Request(np.arange(1, 20, dtype=np.int32), 12, rid=i)
                  for i in range(3)])
        snap = obs.snapshot()["counters"]
        assert all(n in snap for n in names)
        computed, live = (obs.counter(n).value() - b
                          for n, b in zip(names, before))
        if layout == "dense":
            assert computed == live == 0
        else:
            assert computed >= live > 0 and computed % 16 == 0


# -- PR 2 satellites: span ring, publish staleness, merged prometheus,
# -- utils-metrics dedupe, xla telemetry ------------------------------------

_PROM_LINE = __import__("re").compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]+="[^"]*"(,[a-zA-Z_]+="[^"]*")*\})?'
    r" (NaN|\+Inf|-?[0-9].*)$")


class TestSpanRing:
    def test_overflow_keeps_newest_and_counts_dropped(self):
        t = obs.SpanTracer(max_events=3)
        for i in range(8):
            with t.span(f"s{i}"):
                pass
        names = [e["name"] for e in t.events()]
        assert names == ["s5", "s6", "s7"]  # the NEWEST spans survive
        assert t.dropped == 5
        with t.span("s8"):
            pass
        assert [e["name"] for e in t.events()] == ["s6", "s7", "s8"]
        assert t.dropped == 6


class TestPublishStaleness:
    def test_publish_stamps_and_collect_ages_and_drops(self):
        import time as _t

        server, client = _coord_pair()
        try:
            fresh_reg = obs.MetricRegistry()
            fresh_reg.counter("c").inc(1)
            stale_reg = obs.MetricRegistry()
            stale_reg.counter("c").inc(9)
            obs.MetricsPublisher(client, 0, fresh_reg).publish()
            # rank 1 published "long ago": rewrite its stamp backwards
            snap = obs.MetricsPublisher(client, 1, stale_reg).publish()
            snap["published_at"] = _t.time() - 300
            client.set("obs/metrics/1", json.dumps(snap).encode())

            got = obs.collect(client)
            assert got[0]["age_s"] == pytest.approx(0, abs=5)
            assert got[1]["age_s"] == pytest.approx(300, abs=5)

            # max_age_s DROPS the dead rank's leftover snapshot
            only_fresh = obs.collect(client, max_age_s=60)
            assert sorted(only_fresh) == [0]
            merged = obs.collect_and_merge(client, max_age_s=60)
            assert merged["counters"]["c"]["value"] == 1
            # without the cutoff the merged view keeps per-rank ages
            both = obs.collect_and_merge(client)
            assert both["counters"]["c"]["value"] == 10
            assert both["ages"]["1"] > 200
        finally:
            client.close()
            server.stop()

    def test_pre_stamp_snapshot_age_is_none_and_never_dropped(self):
        server, client = _coord_pair()
        try:
            reg = obs.MetricRegistry()
            reg.counter("c").inc(2)
            snap = obs.MetricsPublisher(client, 0, reg).publish()
            del snap["published_at"]  # a publisher from before the stamp
            client.set("obs/metrics/0", json.dumps(snap).encode())
            got = obs.collect(client, max_age_s=1)
            assert got[0]["age_s"] is None  # unknown age: kept, not dropped
        finally:
            client.close()
            server.stop()


class TestMergedPrometheus:
    def test_merged_snapshot_renders_valid_exposition(self):
        """A merged cross-rank snapshot (per_worker maps, ages) must render
        to prometheus text where EVERY non-comment line matches the
        exposition grammar — no dict reprs, no unlabeled junk."""
        regs = [obs.MetricRegistry() for _ in range(2)]
        for rank, reg in enumerate(regs):
            reg.counter("train/steps", unit="steps").inc(10 * (rank + 1))
            reg.gauge("queue").set(rank)
            reg.histogram("lat", unit="s").record([1.0, 4.0])
        merged = obs.merge_snapshots(
            {r: reg.snapshot() for r, reg in enumerate(regs)})
        text = obs.to_prometheus(merged)
        for ln in text.splitlines():
            if not ln or ln.startswith("#"):
                continue
            assert _PROM_LINE.match(ln), f"invalid exposition line: {ln!r}"
        # aggregate + one labeled sample per rank
        assert "train_steps 30.0" in text
        assert 'train_steps{worker="0"} 10.0' in text
        assert 'train_steps{worker="1"} 20.0' in text
        assert 'queue{worker="1"} 1.0' in text
        # merged histograms keep exact cumulative buckets
        assert 'lat_bucket{le="+Inf"} 4' in text

    def test_plain_snapshot_unchanged_no_worker_labels(self):
        r = _registry()
        r.counter("c").inc(3)
        text = obs.to_prometheus(r.snapshot())
        assert "c 3.0" in text and "worker=" not in text


class TestUtilsMetricsDedupe:
    def test_throughput_meter_feeds_obs_gauges(self):
        from tpudist.utils.metrics import ThroughputMeter

        m = ThroughputMeter(warmup_steps=1)
        m.start()
        for _ in range(4):
            m.step(64)
        snap = obs.snapshot()
        assert snap["gauges"]["throughput/items_per_sec"]["value"] == \
            pytest.approx(m.items_per_sec)
        assert snap["gauges"]["throughput/steps"]["value"] == 4

    def test_stopwatch_obs_name_records_histogram(self):
        from tpudist.utils.metrics import Stopwatch

        reg_before = obs.snapshot()["histograms"].get(
            "test_obs/sw", {"count": 0})["count"]
        sw = Stopwatch(obs_name="test_obs/sw")
        sw.elapsed()
        sw.elapsed()
        h = obs.snapshot()["histograms"]["test_obs/sw"]
        assert h["count"] == reg_before + 2

    def test_stopwatch_default_stays_out_of_obs(self):
        from tpudist.utils.metrics import Stopwatch

        before = set(obs.snapshot()["histograms"])
        Stopwatch().elapsed()
        assert set(obs.snapshot()["histograms"]) == before


class TestXlaTelemetry:
    def test_note_compile_counts_and_records(self):
        from tpudist.obs import xla

        reg = obs.MetricRegistry()
        xla.note_compile(0.5, registry=reg)
        xla.note_compile(1.5, registry=reg)
        snap = reg.snapshot()
        assert snap["counters"]["xla/compiles"]["value"] == 2
        assert snap["histograms"]["xla/compile_seconds"]["count"] == 2
        # the global recorder got the breadcrumbs
        kinds = [e["kind"] for e in obs.recorder.events()]
        assert kinds.count("xla_compile") >= 2

    def test_compile_watch_uses_per_site_names(self):
        from tpudist.obs import xla

        reg = obs.MetricRegistry()
        with xla.compile_watch("ici", registry=reg) as w:
            pass
        snap = reg.snapshot()
        assert snap["counters"]["xla/compiles_ici"]["value"] == 1
        assert "xla/compiles" not in snap["counters"]  # no double-count
        assert w.seconds >= 0

    def test_monitoring_listener_sees_backend_compiles(self):
        """install_compile_telemetry + a fresh jit compile: the listener
        must bump xla/compiles without any call-site instrumentation."""
        from tpudist.obs import xla

        reg = obs.registry
        if not xla.install_compile_telemetry(reg):
            pytest.skip("this jax has no monitoring hooks")
        before = reg.counter("xla/compiles").value()
        # a shape this suite never compiles elsewhere -> a real compile
        # (the persistent cache may serve it, which still fires the event)
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 13))).block_until_ready()
        assert reg.counter("xla/compiles").value() >= before

    def test_cost_flops_and_note_step(self):
        from tpudist.obs import xla

        lowered = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8)))
        flops = xla.cost_flops(lowered)
        assert flops and flops > 0
        reg = obs.MetricRegistry()
        tflops = xla.note_step(0.001, flops, registry=reg)
        assert tflops == pytest.approx(flops / 0.001 / 1e12)
        assert reg.snapshot()["gauges"]["xla/step_tflops"]["value"] == \
            pytest.approx(tflops)
        # no step signal -> no gauge write
        assert xla.note_step(0.0, flops, registry=reg) is None
        assert xla.note_step(0.001, None, registry=reg) is None

    def test_cost_flops_compiles_a_lowered_stage_that_has_no_analysis(self):
        """On a PJRT-plugin backend (the TPU) ``Lowered.cost_analysis()``
        is None and only the compiled program reports FLOPs."""
        from tpudist.obs import xla

        class Compiled:
            def cost_analysis(self):
                return [{"flops": 42.0}]

        class Lowered:
            def cost_analysis(self):
                return None

            def compile(self):
                return Compiled()

        assert xla.cost_flops(Lowered()) == 42.0
        assert xla.cost_flops(Compiled()) == 42.0

    def test_memory_and_peak_degrade_on_cpu(self):
        from tpudist.obs import xla

        # CPU reports no allocator stats and is not in the peak table:
        # everything degrades to None/{} instead of raising
        assert xla.update_memory_gauges(registry=obs.MetricRegistry()) == {}
        assert xla.peak_tflops() is None
        assert xla.mfu(100.0) is None
        assert xla.peak_tflops(
            type("D", (), {"device_kind": "TPU v5e"})()) == 197.0
        assert xla.mfu(98.5, type("D", (), {"device_kind": "TPU v5e"})()) \
            == pytest.approx(0.5)


class TestWindowedHistogram:
    """Sliding-window mode: observations expire so control loops see the
    last ``window_s`` seconds, not the process lifetime."""

    def _h(self, window_s=10.0):
        clock = {"t": 0.0}
        h = obs.Histogram("w", unit="s", window_s=window_s,
                          clock=lambda: clock["t"])
        return h, clock

    def test_unwindowed_is_lifetime(self):
        h = obs.Histogram("h")
        assert h.window_s is None
        h.record(4.0)
        s = h.summary()
        assert s["count"] == 1

    def test_rejects_nonpositive_window(self):
        with pytest.raises(ValueError):
            obs.Histogram("h", window_s=0.0)

    def test_fresh_samples_visible(self):
        h, clock = self._h(window_s=10.0)
        h.record(4.0)
        h._fold(h._take_pending())
        assert h._snap()["count"] == 1
        assert hist_quantile(h._snap(), 0.99) == 4.0

    def test_old_samples_expire(self):
        h, clock = self._h(window_s=10.0)
        h.record(64.0)                      # the "old spike"
        h._fold(h._take_pending())
        clock["t"] = 4.0                    # still inside half-window span
        h.record(64.0)
        h._fold(h._take_pending())
        assert h._snap()["count"] == 2
        clock["t"] = 12.0                   # first gen now > window old
        h.record(1.0)
        h._fold(h._take_pending())
        snap = h._snap()
        # both 64.0 samples landed in the generation started at t=0,
        # which expired at t>=10; only the fresh 1.0 remains
        assert snap["count"] == 1
        assert hist_quantile(snap, 0.99) == 1.0
        assert snap["max"] == 1.0

    def test_quiet_gap_expires_everything(self):
        h, clock = self._h(window_s=10.0)
        h.record(64.0)
        h._fold(h._take_pending())
        clock["t"] = 100.0                  # long idle gap, no traffic
        assert h._snap()["count"] == 0
        assert math.isnan(hist_quantile(h._snap(), 0.99))

    def test_window_covers_at_least_half(self):
        # samples newer than window_s/2 are never expired
        h, clock = self._h(window_s=10.0)
        clock["t"] = 6.0
        h.record(8.0)
        h._fold(h._take_pending())
        clock["t"] = 10.9                   # sample is 4.9s old < half
        assert h._snap()["count"] == 1

    def test_snapshot_wire_format_carries_window(self):
        h, clock = self._h(window_s=10.0)
        h.record(2.0)
        h._fold(h._take_pending())
        snap = h._snap()
        assert snap["window_s"] == 10.0
        assert set(snap) >= {"unit", "growth", "count", "sum", "min",
                             "max", "zero", "buckets"}
        # merged snapshots still accept the shape
        merged = obs.merge_snapshots(
            {0: {"histograms": {"w": snap}},
             1: {"histograms": {"w": snap}}})
        assert merged["histograms"]["w"]["count"] == 2

    def test_summary_keeps_the_lifetime_totals(self):
        """What the window forgets stays countable: a difference of two
        ``lifetime_*`` readings is an interval's own total."""
        h, clock = self._h(window_s=10.0)
        h.record(64.0)
        h.record([1.0, 3.0])
        first = h.summary()
        assert (first["count"], first["lifetime_count"]) == (3, 3)
        assert first["sum"] == first["lifetime_sum"] == 68.0
        clock["t"] = 100.0                  # the window drops all three
        h.record(2.0)
        later = h.summary()
        assert (later["count"], later["sum"]) == (1, 2.0)
        assert later["lifetime_count"] - first["lifetime_count"] == 1
        assert later["lifetime_sum"] - first["lifetime_sum"] == 2.0
        # the wire format and an unwindowed histogram are as they were
        assert "lifetime_sum" not in h._snap()
        assert "lifetime_sum" not in obs.Histogram("h").summary()

    def test_registry_window_kwarg(self):
        r = obs.MetricRegistry()
        h = r.histogram("serve/queue_wait_s", unit="s", window_s=30.0)
        assert h.window_s == 30.0
        # repeat registration returns the SAME windowed metric
        assert r.histogram("serve/queue_wait_s") is h
