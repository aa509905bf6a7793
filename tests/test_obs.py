"""Wire-level telemetry subsystem tests (repro/obs + the closed loop).

Core guarantees under test:
  * the tracer is ZERO-cost when disabled (module helpers no-op, shared
    null context, no events) and bounded when enabled (ring buffer drops
    oldest, counts drops);
  * exporter: JSONL round-trips through the schema validator;
  * one clock: an enabled span lands on the profiler's host timeline
    (``jax.profiler.TraceAnnotation``); a disabled one opens nothing;
  * probes key ring pairs EXACTLY like ``collective_counts
    (by_pairs=True)`` keys the HLO audit — one vocabulary between the
    measurement and the compiled-program launch table;
  * ``bandwidth>=X`` policy rules close the loop: two different probe
    measurements flip the resolved codec between epochs, while a no-probe
    run resolves bit-identically to the static PR-7 rule engine;
  * tracing ON does not change serve-engine outputs or its jit caches.
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.policy import (CompressionPolicy, parse_policy_rules,
                               quant_policy, resolve_policy, topk_policy)
from repro.launch.mesh import make_mesh
from repro.obs import trace
from repro.obs.export import (EVENT_SCHEMA, to_jsonl, validate_events,
                              validate_jsonl)
from repro.obs.probes import (LinkMeasurement, boundary_bandwidth,
                              pairs_key, ring_pairs)
from repro.obs.quality import QualityTap, feedback_norms, relative_error


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts AND ends with the global tracer disabled."""
    trace.disable()
    yield
    trace.disable()


class TestTracer:
    def test_span_counter_instant_phases(self):
        tr = trace.enable()
        with trace.span("a.span", cat="t", k=1) as args:
            args["late"] = 2
        trace.counter("a.counter", cat="t", depth=3)
        trace.instant("a.instant", cat="t", tag="x")
        evs = tr.drain()
        assert [(e.name, e.ph) for e in evs] == [
            ("a.span", "X"), ("a.counter", "C"), ("a.instant", "i")]
        assert evs[0].args == {"k": 1, "late": 2}
        assert evs[0].dur >= 0 and evs[0].ts >= 0
        assert tr.drain() == []                    # drain pops

    def test_ring_buffer_drops_oldest_and_counts(self):
        tr = trace.enable(capacity=4)
        for i in range(7):
            trace.instant(f"e{i}")
        assert tr.dropped == 3
        assert [e.name for e in tr.snapshot()] == ["e3", "e4", "e5", "e6"]
        assert tr.stats() == {"buffered": 4, "dropped": 3, "capacity": 4}

    def test_disabled_helpers_are_noops(self):
        assert trace.get_tracer() is None
        trace.counter("x", v=1)
        trace.instant("x")
        with trace.span("x") as args:
            args["k"] = 1                          # writes to shared null
        # enabling afterwards shows none of the above was recorded
        tr = trace.enable()
        assert tr.snapshot() == []

    def test_span_times_the_block(self):
        import time
        tr = trace.enable()
        with trace.span("timed"):
            time.sleep(0.01)
        (ev,) = tr.drain()
        assert ev.dur >= 0.009

    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            trace.enable(capacity=0)

    def test_span_lands_on_the_profiler_host_timeline(self, tmp_path):
        import glob
        from jax.profiler import ProfileData
        trace.enable()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("obs.test_span", cat="t"):
                jnp.ones(8).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        host = {e.name for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:")
                for ln in p.lines for e in ln.events}
        assert "obs.test_span" in host

    def test_disabled_span_opens_no_annotation(self, monkeypatch):
        opened = []

        class Fake:
            def __init__(self, name, **kw):
                opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
        with trace.span("off.span"):
            pass
        assert opened == []
        trace.enable()
        with trace.span("on.span"):
            pass
        assert opened == ["on.span"]


class TestExport:
    def _events(self):
        tr = trace.enable()
        with trace.span("s", cat="train", loss=1.5):
            pass
        trace.counter("c", cat="serve", depth=2)
        trace.instant("i", cat="wire", codec="q8")
        return tr.drain()

    def test_jsonl_roundtrip_validates(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        assert to_jsonl(self._events(), p) == 3
        assert validate_jsonl(p) == 3
        rows = [json.loads(x) for x in open(p)]
        assert [r["ph"] for r in rows] == ["X", "C", "i"]
        assert set(rows[0]) == set(EVENT_SCHEMA)

    def test_validator_rejects_bad_events(self):
        ok = {"name": "n", "cat": "c", "ph": "i", "ts_us": 1.0,
              "dur_us": 0.0, "args": {}}
        assert validate_events([ok]) == 1
        for bad, msg in [
            ({**ok, "ph": "Z"}, "phase"),
            ({**ok, "ts_us": -1.0}, "negative"),
            ({**ok, "args": "notadict"}, "args"),
            ({k: v for k, v in ok.items() if k != "name"}, "missing"),
            ({**ok, "extra": 1}, "unknown"),
            ({**ok, "ts_us": True}, "ts_us"),      # bool is not numeric
        ]:
            with pytest.raises(ValueError, match=msg):
                validate_events([bad])


class TestQuality:
    def test_relative_error_zero_for_identity(self):
        x = jnp.ones((4, 8), jnp.float32)
        none = CompressionPolicy(num_stages=2).boundary.fw
        assert relative_error(x, none) == 0.0
        q4 = quant_policy(4, 4).fw
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        assert 0.0 < relative_error(x, q4) < 1.0

    def test_feedback_norms_skips_nonfloat(self):
        state = {"resid": jnp.ones((2, 3)), "ids": jnp.zeros((2,), jnp.int32),
                 "empty": jnp.zeros((0,))}
        norms = feedback_norms(state)
        assert set(norms) == {"['resid']"}
        assert norms["['resid']"] == pytest.approx(np.sqrt(6.0))

    def test_tap_gates_on_tracer_and_stride(self):
        tap = QualityTap((2, 16), every=2, dtype=jnp.float32)
        pol = CompressionPolicy(num_stages=3, boundary=quant_policy(8, 8))
        assert tap.maybe_sample(0, pol) is None    # tracing off
        tr = trace.enable()
        assert tap.maybe_sample(1, pol) is None    # off-stride
        rows = tap.maybe_sample(2, pol)
        assert [r["boundary"] for r in rows] == [0, 1]
        assert all(0.0 < r["fw_rel_err"] < 1.0 for r in rows)
        names = {e.name for e in tr.drain()}
        assert "quality.boundary0" in names
        assert "quality.codec.boundary1" in names

    def test_tap_validates_stride(self):
        with pytest.raises(ValueError, match="every"):
            QualityTap((2, 4), every=0)


class TestProbeKeying:
    """probes.pairs_key and dryrun.collective_counts(by_pairs=True) must
    speak the same ring vocabulary (pure parsers — no devices needed)."""

    HLO = """
  ENTRY main {
    p0 = bf16[8]{0} parameter(0)
    cp1 = bf16[8]{0} collective-permute(p0), source_target_pairs={{0,2},{2,0},{1,3},{3,1}}
    cp2 = bf16[8]{0} collective-permute(cp1), source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
    cp3 = bf16[8]{0} collective-permute-start(cp2), source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
    ar = bf16[8]{0} all-reduce(p0), replica_groups={{0,1,2,3}}
  }
    """

    def test_by_pairs_separates_rings(self):
        from repro.launch.dryrun import collective_counts
        counts = collective_counts(self.HLO, by_pairs=True)
        dp_ring = "collective-permute|{{0,2},{1,3},{2,0},{3,1}}"
        pp_ring = "collective-permute|{{0,1},{1,0},{2,3},{3,2}}"
        # NOTE: keys preserve the HLO's own pair order; the dp ring above
        # appears exactly as printed in the canned text
        assert counts["collective-permute|{{0,2},{2,0},{1,3},{3,1}}"] == 1
        assert counts[pp_ring] == 2                # -start counts once
        assert counts["all-reduce|{{0,1,2,3}}"] == 1
        assert dp_ring not in counts               # sorted != HLO order

    def test_pairs_key_is_sorted_and_formatted(self):
        key = pairs_key({(2, 0), (0, 2), (3, 1), (1, 3)})
        assert key == "{{0,2},{1,3},{2,0},{3,1}}"

    def test_ring_pairs_on_1d_mesh(self):
        mesh = make_mesh((jax.device_count(),), ("stage",))
        n = jax.device_count()
        pairs = ring_pairs(mesh, "stage")
        ids = [d.id for d in np.asarray(mesh.devices).ravel()]
        want = {(ids[r], ids[(r + 1) % n]) for r in range(n)}
        assert pairs == want

    def test_boundary_bandwidth_accessors(self):
        m = LinkMeasurement("stage", "{{0,1}}", payload_bytes=1000,
                            seconds=0.001)
        assert m.bytes_per_s == pytest.approx(1e6)
        assert boundary_bandwidth(None) is None
        assert boundary_bandwidth(2.5e9) == 2.5e9
        assert boundary_bandwidth(m) == pytest.approx(1e6)
        slow = LinkMeasurement("data", "{{0,1}}", 1000, 0.01)
        assert boundary_bandwidth({"stage": m, "data": slow}) \
            == pytest.approx(1e6)                  # stage axis preferred
        assert boundary_bandwidth({"data": slow, "x": m}) \
            == pytest.approx(1e5)                  # else slowest ring
        assert boundary_bandwidth({}) is None


class TestBandwidthRules:
    def test_parse_and_resolve_with_bandwidth(self):
        rules = parse_policy_rules("none@bandwidth>=5e9;q4@bandwidth<1e6;q8")
        sizes = 4096
        # no probe: bandwidth terms never fire -> q8 everywhere, exactly
        # the static resolution (degenerate no-probe identity)
        static = resolve_policy(rules, sizes)
        assert static.boundary.fw.name == "q8"
        assert resolve_policy(rules, sizes, bandwidth=None).name \
            == static.name
        fast = resolve_policy(rules, sizes, bandwidth=6e9)
        assert fast.boundary.fw.name == "none"
        slow = resolve_policy(rules, sizes, bandwidth=1e3)
        assert slow.boundary.fw.name == "q4"

    def test_bandwidth_conds_in_rule_name(self):
        rules = parse_policy_rules("q8@bandwidth>=1e9")
        assert "bandwidth>=1e+09" in rules.rules[0].name

    def test_integer_thresholds_still_required(self):
        with pytest.raises(ValueError, match="integers"):
            parse_policy_rules("q8@size>=1.5")

    def test_unknown_cond_rejected(self):
        with pytest.raises(ValueError, match="bad rule condition"):
            parse_policy_rules("q8@latency>=3")


class TestClosedLoop:
    """The tentpole acceptance: probe measurements flip the chosen codec
    between epochs; without a probe the run matches static resolution."""

    CFG = None

    @classmethod
    def _cfg_data(cls):
        from repro.data.synthetic import LMData
        from repro.models.config import ModelConfig
        cfg = ModelConfig(
            arch_id="obs-loop", family="dense", num_layers=4, d_model=32,
            num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
            vocab_size=64, pos_embed="rope", norm="layernorm", mlp="gelu",
            max_seq=16)
        data = LMData(num_train=32, num_test=8, seq_len=16, vocab=64)
        return cfg, data

    def test_probe_flips_codec_between_epochs(self):
        from repro.train.loop import run_lm_experiment
        cfg, data = self._cfg_data()
        rules = parse_policy_rules("none@bandwidth>=5e9;q8")
        meas = iter([6e9, 1e3, 1e3])               # fast, then congested
        tr = trace.enable()
        res = run_lm_experiment(cfg, rules, epochs=3, batch=8, data=data,
                                bandwidth_probe=lambda: next(meas))
        assert len(res.policy_curve) == 3
        assert res.policy_curve[0] != res.policy_curve[1]  # the flip
        assert res.policy_curve[1] == res.policy_curve[2]  # ...then held
        flips = [e for e in tr.drain() if e.name == "policy.flip"]
        assert len(flips) == 1 and flips[0].args["epoch"] == 1
        assert all(np.isfinite(res.train_curve))

    def test_no_probe_matches_static_resolution_exactly(self):
        from repro.train.loop import run_lm_experiment
        cfg, data = self._cfg_data()
        rules = parse_policy_rules("none@bandwidth>=5e9;q8")
        static = resolve_policy(rules, data.seq_len * cfg.d_model)
        r_rules = run_lm_experiment(cfg, rules, epochs=1, batch=8,
                                    data=data)
        r_static = run_lm_experiment(cfg, static, epochs=1, batch=8,
                                     data=data)
        assert r_rules.policy_curve == [static.name]
        assert r_rules.train_curve == r_static.train_curve  # bit-identical
        assert r_rules.loss_on == r_static.loss_on


class TestServeTracingIdentity:
    """Tracing ON must not change tokens or compile counts."""

    def test_tokens_and_jit_caches_unchanged(self):
        from repro.configs.registry import get
        from repro.serve.engine import ContinuousEngine
        cfg = get("gpt2-small", smoke=True)
        from repro.models import transformer
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        pol = CompressionPolicy(num_stages=2, boundary=topk_policy(0.10))
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, cfg.vocab_size, l).astype(np.int32)
                   for l in (5, 11, 7)]

        def serve():
            eng = ContinuousEngine(params, cfg, pol, num_slots=2,
                                   max_seq=64)
            eng.warmup()
            warm = eng.compile_stats()
            for i, p in enumerate(prompts):
                eng.submit(p, max_new_tokens=4, seed=i)
            done = eng.drain()
            assert eng.compile_stats() == warm     # no tick recompiles
            return {r.req_id: r.out.copy() for r in done}

        base = serve()
        tr = trace.enable()
        traced = serve()
        for rid in base:
            np.testing.assert_array_equal(base[rid], traced[rid])
        names = {e.name for e in tr.snapshot()}
        assert {"serve.decode", "serve.sched",
                "serve.request_done"} <= names


class TestSchedulerSnapshot:
    def test_snapshot_counts(self):
        from repro.serve.scheduler import Scheduler
        s = Scheduler(3)
        assert s.snapshot() == {"queued": 0, "active_slots": 0,
                                "free_slots": 3, "completed": 0}
        for i in range(4):
            s.submit(np.array([1, 2], np.int32), max_new_tokens=1)
        placed = s.fills()
        assert len(placed) == 3
        snap = s.snapshot()
        assert snap["queued"] == 1 and snap["active_slots"] == 3
        assert snap["free_slots"] == 0
        s.started(placed[0][0], 7)                 # 1-token req completes
        snap = s.snapshot()
        assert snap["completed"] == 1 and snap["free_slots"] == 1
