"""SLO engine (mlcomp_tpu/obs/slo.py): burn-rate math against
synthetic histories, breach/recover transitions with their
flight-recorder instants, config override + bad-config rejection —
pure host code, no jax."""

import pytest

from mlcomp_tpu.obs.history import MetricsHistory
from mlcomp_tpu.obs.metrics import Registry
from mlcomp_tpu.obs.slo import (
    DEFAULT_SLOS,
    SLOConfigError,
    SLOEngine,
    validate_config,
)
from mlcomp_tpu.utils.trace import Tracer


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def make_engine(config=None, fast_s=10.0, slow_s=30.0):
    reg = Registry()
    clock = Clock()
    hist = MetricsHistory(reg, interval_s=5.0, clock=clock, start=False)
    cfg = dict(config or {})
    cfg.setdefault("windows", {"fast_s": fast_s, "slow_s": slow_s})
    rec = Tracer()
    slo = SLOEngine(hist, config=cfg, registry=reg, recorder=rec)
    return reg, hist, clock, slo, rec


def tick(hist, clock, slo, dt=5.0):
    clock.t += dt
    hist.sample_now()
    slo.evaluate()


# ------------------------------------------------------------ burn math


def test_availability_burn_rate_math():
    reg, hist, clock, slo, rec = make_engine()
    g = reg.gauge("mlcomp_engine_healthy", "")
    # 2 healthy + 2 unhealthy samples at 5 s ticks: the 30 s slow
    # window holds all four (bad fraction 0.5), the 10 s fast window
    # only the trailing three (bad fraction 2/3) — over a 0.001 budget
    for v in (1, 1, 0, 0):
        g.set(v)
        tick(hist, clock, slo)
    st = slo.status()["slos"]["engine_healthy"]
    assert st["burn_rate"]["fast"] == pytest.approx(2 / 3 / 0.001,
                                                   rel=0.01)
    assert st["burn_rate"]["slow"] == pytest.approx(500.0, rel=0.01)
    assert st["breached"]


def test_disabled_slo_stays_disabled_through_the_engine():
    # regression: SLOEngine validates the RAW config itself; feeding
    # it a pre-validated dict (which drops disabled entries without a
    # marker) used to re-merge the defaults and resurrect them
    reg, hist, clock, slo, rec = make_engine(config={
        "slos": {"per_token_p50": {"enabled": False}},
    })
    assert "per_token_p50" not in slo.slos
    tick(hist, clock, slo)
    assert "per_token_p50" not in slo.status()["slos"]


def test_reject_rate_is_a_ratio_over_the_engines_counter():
    # accepted requests are counted in mlcomp_engine_requests_total:
    # one 429 among many successes must be a RATIO, not a
    # denominator-free guaranteed 1.0 breach
    reg, hist, clock, slo, rec = make_engine()
    reg.counter(
        "mlcomp_serving_requests_rejected_total", "",
        labelnames=("reason",),
    ).inc(1, reason="queue_full")
    reg.counter("mlcomp_engine_requests_total", "").inc(99)
    tick(hist, clock, slo)
    st = slo.status()["slos"]["reject_rate"]
    assert st["value"] == pytest.approx(0.01)
    assert not st["breached"]


def test_ratio_burn_rate_sums_labelsets_and_idles_at_zero():
    reg, hist, clock, slo, rec = make_engine()
    # no traffic at all: an idle service burns nothing
    tick(hist, clock, slo)
    assert slo.status()["slos"]["reject_rate"]["burn_rate"]["fast"] == 0.0
    rej = reg.counter(
        "mlcomp_serving_requests_rejected_total", "",
        labelnames=("reason",),
    )
    ok = reg.counter("mlcomp_engine_requests_total", "")
    rej.inc(2, reason="queue_full")
    rej.inc(1, reason="concurrency")
    ok.inc(7)
    tick(hist, clock, slo)
    st = slo.status()["slos"]["reject_rate"]
    # 3 rejected of 10 submitted = 0.3 bad fraction / 0.01 budget
    assert st["burn_rate"]["fast"] == pytest.approx(30.0)
    assert st["value"] == pytest.approx(0.3)


def test_latency_quantile_burn_counts_bad_intervals():
    reg, hist, clock, slo, rec = make_engine(config={
        "slos": {"ttft_p95": {"threshold_ms": 100.0, "budget": 0.5}},
    })
    h = reg.histogram(
        "mlcomp_engine_ttft_ms", "", buckets=(10.0, 100.0, 1000.0)
    )
    # interval 1: all fast (p95 <= 100) -> good
    for _ in range(10):
        h.observe(50)
    tick(hist, clock, slo)
    assert not slo.status()["slos"]["ttft_p95"]["breached"]
    # intervals 2+3: all slow -> 2 bad of 3 observed intervals,
    # fraction 2/3 over budget 0.5 -> burn ~1.33 on both windows
    for _ in range(2):
        for _ in range(10):
            h.observe(500)
        tick(hist, clock, slo)
    st = slo.status()["slos"]["ttft_p95"]
    assert st["burn_rate"]["fast"] == pytest.approx(2 / 3 / 0.5, rel=0.01)
    assert st["breached"]
    # the live windowed measurement is the slow p95
    assert st["value"] > 100.0


def test_censored_quantiles_count_bad_and_warn_once():
    # observations past the histogram's largest finite bound live in
    # the implicit +Inf bucket: the materialized quantile clamps to
    # the bound, so a threshold AT/ABOVE it could never fire.  Those
    # censored intervals must count as breaching (fail-safe for an
    # alerting path), and the misconfigured threshold warns once.
    import warnings as w

    reg, hist, clock, slo, rec = make_engine(config={
        "slos": {"ttft_p95": {"threshold_ms": 5000.0, "budget": 0.5}},
    })
    h = reg.histogram("mlcomp_engine_ttft_ms", "", buckets=(10.0, 100.0))
    for _ in range(2):
        for _ in range(10):
            h.observe(999999)  # all mass in +Inf
        with w.catch_warnings(record=True) as caught:
            w.simplefilter("always")
            tick(hist, clock, slo)
    st = slo.status()["slos"]["ttft_p95"]
    assert st["breached"], st  # censored intervals counted bad
    # warned exactly once across the two evaluations
    msgs = [str(c.message) for c in caught
            if "largest finite bucket bound" in str(c.message)]
    assert not msgs  # second tick: already warned
    assert "ttft_p95" in slo._censor_warned


def test_intervals_without_observations_do_not_count():
    reg, hist, clock, slo, rec = make_engine()
    reg.histogram("mlcomp_engine_ttft_ms", "", buckets=(10.0, 2500.0))
    for _ in range(4):  # empty intervals only
        tick(hist, clock, slo)
    st = slo.status()["slos"]["ttft_p95"]
    assert st["burn_rate"] == {"fast": 0.0, "slow": 0.0}
    assert not st["breached"]


# ------------------------------------------------- transitions + surfaces


def test_breach_and_recover_transitions_record_instants():
    reg, hist, clock, slo, rec = make_engine(fast_s=10.0, slow_s=30.0)
    g = reg.gauge("mlcomp_engine_healthy", "")
    g.set(0)
    tick(hist, clock, slo)
    assert slo.status()["breached"] == ["engine_healthy"]
    assert slo.status()["slos"]["engine_healthy"]["breaches"] == 1
    # stays breached: no SECOND breach counted, no second instant
    tick(hist, clock, slo)
    assert slo.status()["slos"]["engine_healthy"]["breaches"] == 1
    # healthy again; the bad samples age out of both windows
    g.set(1)
    for _ in range(8):
        tick(hist, clock, slo)
    assert slo.status()["breached"] == []
    names = [e["name"] for e in rec.events]
    assert names.count("slo_breach") == 1
    assert names.count("slo_recover") == 1
    breach = next(e for e in rec.events if e["name"] == "slo_breach")
    assert breach["args"]["slo"] == "engine_healthy"
    assert breach["args"]["burn_fast"] > 1.0


def test_gauges_published_to_registry():
    reg, hist, clock, slo, rec = make_engine()
    reg.gauge("mlcomp_engine_healthy", "").set(0)
    tick(hist, clock, slo)
    text = reg.render()
    assert 'mlcomp_slo_breached{slo="engine_healthy"} 1' in text
    assert 'mlcomp_slo_breaches_total{slo="engine_healthy"} 1' in text
    assert 'mlcomp_slo_burn_rate{slo="engine_healthy",window="fast"}' in text


def test_summary_is_the_healthz_block():
    reg, hist, clock, slo, rec = make_engine()
    tick(hist, clock, slo)
    s = slo.summary()
    assert set(s) == {"evaluations", "breached", "burn_rate"}
    assert set(s["burn_rate"]) == set(DEFAULT_SLOS)


# ------------------------------------------------------------ config


def test_override_merges_over_defaults():
    cfg = validate_config({
        "burn_threshold": 2.0,
        "windows": {"fast_s": 60},
        "slos": {
            "ttft_p95": {"threshold_ms": 500.0},
            "per_token_p50": {"enabled": False},
            "custom_p99": {
                "kind": "latency_quantile",
                "metric": "mlcomp_engine_per_token_ms",
                "q": 0.99, "threshold_ms": 50.0, "budget": 0.02,
            },
        },
    })
    assert cfg["burn_threshold"] == 2.0
    assert cfg["windows"] == {"fast_s": 60.0, "slow_s": 3600.0}
    assert cfg["slos"]["ttft_p95"]["threshold_ms"] == 500.0
    assert cfg["slos"]["ttft_p95"]["q"] == 0.95  # default kept
    assert "per_token_p50" not in cfg["slos"]  # disabled
    assert cfg["slos"]["custom_p99"]["budget"] == 0.02


@pytest.mark.parametrize("bad", [
    "not a dict",
    {"bogus_key": 1},
    {"windows": {"fast_s": -1}},
    {"windows": {"fast_s": 600, "slow_s": 60}},  # fast >= slow
    {"burn_threshold": 0},
    {"slos": "nope"},
    {"slos": {"ttft_p95": {"budget": 2.0}}},
    {"slos": {"ttft_p95": {"no_such_knob": 1}}},
    {"slos": {"fresh": {"budget": 0.1}}},  # new objective, no kind
    {"slos": {"fresh": {"kind": "wat", "budget": 0.1}}},
    {"slos": {"fresh": {"kind": "latency_quantile", "budget": 0.1}}},
    {"slos": {"fresh": {"kind": "ratio", "bad": "x", "total": [],
                        "budget": 0.1}}},
])
def test_bad_config_rejected(bad):
    with pytest.raises(SLOConfigError):
        validate_config(bad)


def test_bad_config_fails_service_construction_shape():
    # the serve layer validates BEFORE spinning up any engine thread;
    # here just pin that SLOEngine itself rejects at construction
    reg = Registry()
    hist = MetricsHistory(reg, start=False)
    with pytest.raises(SLOConfigError):
        SLOEngine(hist, config={"bogus": 1}, registry=reg)
