"""Tests for the observability layer (``repro.obs``).

Covers the three layers plus their driver integrations:

* ``Tracer`` -- span nesting/ordering, thread merging, the bounded
  ring, JSONL round-trips, the Perfetto export schema, and the spans'
  twins on the profiler's clock;
* ``HealthProbes`` -- every probe checked against its host-side
  reference (``train.metrics.consensus_distance``,
  ``core.heterogeneity.local_heterogeneity`` / ``tau_bar_label_skew``,
  dense ``||W||_F``), plus the config/operand error contract;
* probes *in rollouts* -- the load-bearing claim: probe outputs are
  extra scan ys, so the probes-on trajectory is BITWISE the probes-off
  one and ``n_traces`` stays 1 across hot swaps (simulator drivers
  here; the forced-8-device mesh twin runs in a subprocess below);
* ``RetraceGuard`` -- wrap/jit counting exact compiles, budgets,
  excess;
* ``RunReport`` -- build -> write -> ``load_report`` round-trip and
  ``validate_report``'s failure modes;
* the PR's metric satellites -- ``CommMeter.tick``'s deferred-subset
  invariant under fractional fates and the ``MetricLogger`` hardening
  (explicit empty CSV cells, JSONL export, aligned columns,
  ``node_spread`` on empty input).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as T
from repro.core.heterogeneity import local_heterogeneity, tau_bar_label_skew
from repro.core.mixing import (
    BirkhoffSchedule,
    StragglerPolicy,
    arrays_to_matrix,
    schedule_from_matrix,
    schedule_to_arrays,
)
from repro.data.drift import partition_from_pi
from repro.data.synthetic import gaussian_blobs, mean_estimation_clusters
from repro.obs import (
    HealthProbes,
    RetraceGuard,
    RunReport,
    SpanRecord,
    Tracer,
    compute_probes,
    consensus_sq,
    grad_deviation_sq,
    load_report,
    mix_pi_arrays,
    read_jsonl,
    tau_bar_arrays,
    validate_report,
    w_frobenius_sq,
    w_minus_j_frobenius_sq,
)
from repro.train.metrics import CommMeter, MetricLogger, consensus_distance, node_spread
from repro.train.trainer import run_classification, run_mean_estimation

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _shift_schedule(n, coeffs=(0.5, 0.25, 0.25)):
    ids = np.arange(n)
    sched = BirkhoffSchedule(
        coeffs=tuple(coeffs),
        perms=(ids, np.roll(ids, 1), np.roll(ids, -1)),
    )
    return schedule_to_arrays(sched, sched.n_atoms)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_nesting_order_parent_depth():
    tr = Tracer()
    with tr.span("outer", k=3):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    recs = tr.spans()
    # ring orders by COMPLETION: children close before their parent
    assert [r.name for r in recs] == ["inner", "inner", "outer"]
    inner0, inner1, outer = recs
    assert outer.depth == 0 and outer.parent is None
    assert inner0.depth == 1 and inner0.parent == "outer"
    assert inner1.depth == 1 and inner1.parent == "outer"
    assert outer.attrs == {"k": 3}
    # children are contained in the parent on the shared clock
    assert outer.t0 <= inner0.t0 <= inner0.t1 <= inner1.t0 <= inner1.t1 <= outer.t1
    assert outer.duration_s >= 0.0
    assert {r.tid for r in recs} == {threading.get_ident()}
    assert tr.spans("inner") == recs[:2]
    assert tr.total_s("inner") == pytest.approx(
        inner0.duration_s + inner1.duration_s
    )
    s = tr.summary()
    assert s["recorded"] == 3 and s["dropped"] == 0
    assert s["by_name"]["inner"]["count"] == 2


def test_span_exception_still_completes_with_error_attr():
    tr = Tracer()
    with pytest.raises(RuntimeError, match="boom"):
        with tr.span("fails"):
            raise RuntimeError("boom")
    (rec,) = tr.spans()
    assert rec.name == "fails"
    assert "RuntimeError" in rec.attrs["error"]


def test_threads_share_one_timeline():
    tr = Tracer()

    def worker():
        with tr.span("solve"):
            pass

    with tr.span("rollout"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    solve = tr.spans("solve")[0]
    roll = tr.spans("rollout")[0]
    assert solve.tid != roll.tid
    # the worker's span is NOT a child of the main thread's (per-thread
    # stacks), but it lands inside the rollout on the shared clock
    assert solve.parent is None and solve.depth == 0
    assert roll.t0 <= solve.t0 and solve.t1 <= roll.t1


def test_instant_and_disabled_tracer():
    tr = Tracer()
    with tr.span("seg"):
        tr.instant("mark", t=7)
    mark = tr.spans("mark")[0]
    assert mark.t0 == mark.t1
    assert mark.parent == "seg" and mark.depth == 1
    assert mark.attrs == {"t": 7}

    off = Tracer(enabled=False)
    ran = []
    with off.span("seg"):
        ran.append(True)  # the body still runs
    off.instant("mark")
    assert ran == [True]
    assert off.spans() == [] and off.dropped == 0


def test_ring_capacity_eviction_counts_dropped():
    tr = Tracer(capacity=4)
    for i in range(7):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 3
    assert [r.name for r in tr.spans()] == ["s3", "s4", "s5", "s6"]
    assert tr.summary()["recorded"] == 4
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


def test_jsonl_sink_roundtrip(tmp_path):
    sink = str(tmp_path / "trace.jsonl")
    with Tracer(capacity=2, sink_path=sink) as tr:
        for i in range(5):
            with tr.span("s", i=i):
                pass
    # the ring wrapped (capacity 2) but the sink holds everything
    assert tr.dropped == 3
    recs = read_jsonl(sink)
    assert len(recs) == 5
    assert [r.attrs["i"] for r in recs] == list(range(5))
    assert recs[-2:] == tr.spans()
    # dataclass dict round-trip is exact
    for r in recs:
        assert SpanRecord.from_dict(r.to_dict()) == r


def test_jsonl_sink_serializes_numpy_attrs(tmp_path):
    sink = str(tmp_path / "trace.jsonl")
    with Tracer(sink_path=sink) as tr:
        with tr.span("a", x=np.float32(1.5)):  # numpy attr must serialize
            pass
    recs = read_jsonl(sink)
    assert len(recs) == 1 and recs[0].attrs["x"] == 1.5


def _host_events(logdir) -> dict:
    """{name: [events]} on the ``/host:CPU`` plane of the one trace under
    ``logdir``."""
    path = next(logdir.rglob("*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e)
    return events


def test_spans_land_on_the_profiler_clock(tmp_path):
    """Under jax.profiler.trace an enabled tracer's spans and instants are
    host events of the same name, nested as the spans are and no longer
    than the tracer measured; a disabled tracer's leave nothing."""
    tr, off = Tracer(), Tracer(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("obs.outer"):
            with tr.span("obs.inner", k=1):
                time.sleep(0.01)
            tr.instant("obs.mark")
        with off.span("obs.off"):
            off.instant("obs.off_mark")
    events = _host_events(tmp_path)
    assert {"obs.outer", "obs.inner", "obs.mark"} <= set(events)
    assert not {"obs.off", "obs.off_mark"} & set(events)
    (outer,), (inner,) = events["obs.outer"], events["obs.inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    assert 0.01 <= inner.duration_ns * 1e-9 <= tr.spans("obs.inner")[0].duration_s


def test_perfetto_export_schema(tmp_path):
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner", k=2):
            pass
        tr.instant("mark")
    def bg():
        with tr.span("bg"):
            pass

    th = threading.Thread(target=bg)
    th.start()
    th.join()
    path = tr.write_perfetto(str(tmp_path / "trace_perfetto.json"))
    with open(path) as f:
        events = json.load(f)
    phases = [e["ph"] for e in events]
    assert set(phases) <= {"M", "X", "i"}
    # one thread_name metadata event per tid
    tids = {r.tid for r in tr.spans()}
    metas = [e for e in events if e["ph"] == "M"]
    assert len(metas) == len(tids) == 2
    assert all(e["name"] == "thread_name" for e in metas)
    for e in events:
        assert e["pid"] == 1 and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and "ts" in e
        if e["ph"] == "i":
            assert e["s"] == "t"
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"outer", "inner", "bg"} <= names
    assert any(e["ph"] == "i" and e["name"] == "mark" for e in events)


# ---------------------------------------------------------------------------
# Probe math vs host-side references
# ---------------------------------------------------------------------------


def test_consensus_sq_matches_metrics_reference():
    rng = np.random.default_rng(0)
    stack = {
        "w": jnp.asarray(rng.normal(size=(6, 4, 3)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32),
    }
    got = float(consensus_sq(stack))
    ref = float(consensus_distance(stack))
    assert got == ref  # same math, bit for bit
    # and against plain numpy
    want = sum(
        np.sum((np.asarray(v) - np.asarray(v).mean(0, keepdims=True)) ** 2)
        for v in stack.values()
    )
    assert got == pytest.approx(want, rel=1e-5)


def test_grad_deviation_sq_matches_local_heterogeneity():
    rng = np.random.default_rng(1)
    G = rng.normal(size=(8, 5)).astype(np.float32)
    got = float(grad_deviation_sq(jnp.asarray(G)))
    assert got == pytest.approx(local_heterogeneity(G), rel=1e-5)
    # pytree with the node axis leading on every leaf: same value
    split = {"a": jnp.asarray(G[:, :2]), "b": jnp.asarray(G[:, 2:])}
    assert float(grad_deviation_sq(split)) == pytest.approx(got, rel=1e-6)


def test_schedule_probes_match_dense_w():
    n, K = 8, 3
    sa = _shift_schedule(n, coeffs=(0.5, 0.3, 0.2))
    W = arrays_to_matrix(sa)
    rng = np.random.default_rng(2)
    Pi = rng.dirichlet(np.ones(K), size=n)

    got_mix = np.asarray(mix_pi_arrays(sa, jnp.asarray(Pi)))
    assert np.allclose(got_mix, W @ Pi, atol=1e-6)

    assert float(w_frobenius_sq(sa)) == pytest.approx(
        np.linalg.norm(W, "fro") ** 2, rel=1e-5
    )
    J = np.ones((n, n)) / n
    assert float(w_minus_j_frobenius_sq(sa)) == pytest.approx(
        np.linalg.norm(W - J, "fro") ** 2, rel=1e-5
    )
    # W == J: the clamp keeps the probe non-negative at float round-off
    complete = schedule_to_arrays(schedule_from_matrix(T.complete(4)), 6)
    assert 0.0 <= float(w_minus_j_frobenius_sq(complete)) <= 1e-5

    B, sigma2 = 1.7, 0.4
    got_tau = float(tau_bar_arrays(sa, jnp.asarray(Pi), B, sigma2))
    assert got_tau == pytest.approx(
        tau_bar_label_skew(W, Pi, B, sigma2), rel=1e-5
    )


def test_health_probes_config_and_operand_errors():
    assert HealthProbes().names() == ("consensus", "grad_dev")
    full = HealthProbes(consensus=True, grad_dev=True, tau_bar=True)
    assert full.names() == ("consensus", "grad_dev", "tau_bar")
    assert HealthProbes(consensus=False, grad_dev=False, tau_bar=True).names() == (
        "tau_bar",
    )
    with pytest.raises(ValueError, match="every probe disabled"):
        HealthProbes(consensus=False, grad_dev=False, tau_bar=False)
    with pytest.raises(ValueError, match="B must be"):
        HealthProbes(tau_bar=True, B=-1.0)
    with pytest.raises(ValueError, match="sigma2 must be"):
        HealthProbes(tau_bar=True, sigma2=-0.1)

    theta = jnp.ones((4, 2))
    with pytest.raises(ValueError, match="params_stack"):
        compute_probes(HealthProbes(grad_dev=False), grads_stack=theta)
    with pytest.raises(ValueError, match="grads_stack"):
        compute_probes(HealthProbes(consensus=False), params_stack=theta)
    with pytest.raises(ValueError, match="pi_hat"):
        compute_probes(
            HealthProbes(tau_bar=True), params_stack=theta, grads_stack=theta
        )
    out = compute_probes(HealthProbes(), params_stack=theta, grads_stack=theta)
    assert tuple(out) == ("consensus", "grad_dev")
    assert float(out["consensus"]) == 0.0  # identical rows


# ---------------------------------------------------------------------------
# Probes inside the simulator rollouts
# ---------------------------------------------------------------------------


def test_mean_estimation_probes_bitwise_and_tau_bar_value():
    n, K, steps = 8, 4, 30
    task = mean_estimation_clusters(n_nodes=n, K=K)
    Pi = np.eye(K)[np.arange(n) % K].astype(float)
    sa = _shift_schedule(n)
    kw = dict(steps=steps, lr=0.1, batch=2, seed=0, schedule=sa)

    out_off = run_mean_estimation(task, None, **kw)
    probes = HealthProbes(consensus=True, grad_dev=True, tau_bar=True,
                          B=1.3, sigma2=0.5)
    guard = RetraceGuard()
    out_on = run_mean_estimation(
        task, None, probes=probes, pi_hat=Pi, retrace_guard=guard, **kw
    )

    for key in ("mean_sq_error", "max_sq_error", "min_sq_error"):
        assert np.array_equal(out_off[key], out_on[key]), key
    assert out_on["n_traces"] == 1
    assert guard.count("mean_estimation.roll") == 1

    health = out_on["health"]
    assert tuple(health) == ("consensus", "grad_dev", "tau_bar")
    for series in health.values():
        assert series.shape == (steps,) and np.all(np.isfinite(series))
    # no swap and a fixed pi_hat: tau_bar is constant and equals the
    # host-side closed form on the densified schedule
    W = arrays_to_matrix(sa)
    want = tau_bar_label_skew(W, Pi, probes.B, probes.sigma2)
    assert np.allclose(health["tau_bar"], want, rtol=1e-5)


def test_mean_estimation_probe_arg_rejections():
    n = 8
    task = mean_estimation_clusters(n_nodes=n, K=4)
    sa = _shift_schedule(n)
    W = T.ring(n)
    Pi = np.eye(4)[np.arange(n) % 4].astype(float)
    probes = HealthProbes()
    tau_probes = HealthProbes(tau_bar=True)

    with pytest.raises(ValueError, match="retrace-free data plane"):
        run_mean_estimation(task, W, steps=4, probes=probes)  # static W
    with pytest.raises(ValueError, match="scan"):
        run_mean_estimation(
            task, None, steps=4, schedule=sa, rollout="loop", probes=probes
        )
    with pytest.raises(ValueError, match="pi_hat without probes"):
        run_mean_estimation(task, None, steps=4, schedule=sa, pi_hat=Pi)
    with pytest.raises(ValueError, match="needs pi_hat"):
        run_mean_estimation(task, None, steps=4, schedule=sa, probes=tau_probes)
    with pytest.raises(ValueError, match="tau_bar is off"):
        run_mean_estimation(
            task, None, steps=4, schedule=sa, probes=probes, pi_hat=Pi
        )
    with pytest.raises(ValueError, match="pi_hat must be"):
        run_mean_estimation(
            task, None, steps=4, schedule=sa, probes=tau_probes,
            pi_hat=Pi[: n - 1],
        )
    with pytest.raises(TypeError, match="HealthProbes"):
        run_mean_estimation(task, None, steps=4, schedule=sa, probes={"consensus": True})
    with pytest.raises(ValueError, match="bounded-delay"):
        run_mean_estimation(
            task, None, steps=4, schedule=sa, probes=probes,
            staleness=StragglerPolicy(tau_max=1),
        )


def test_classification_probes_bitwise_loss_and_aux_health():
    n, C, d, spn = 6, 3, 8, 16
    X, y = gaussian_blobs(n_samples=10 * spn, num_classes=C, dim=d, seed=7)
    Pi = np.eye(C)[np.arange(n) % C].astype(float)
    idx = partition_from_pi(y, Pi, samples_per_node=spn, seed=8)
    sa = _shift_schedule(n)
    kw = dict(model="linear", steps=12, batch_size=4, lr=0.2, eval_every=6,
              seed=9, schedule=sa)

    log_off = run_classification(X, y, idx, None, **kw)
    guard = RetraceGuard()
    log_on = run_classification(
        X, y, idx, None, probes=HealthProbes(), retrace_guard=guard, **kw
    )
    assert np.array_equal(log_off.column("loss"), log_on.column("loss"))
    assert log_on.aux["n_traces"] == log_off.aux["n_traces"]
    assert guard.count("classification.roll") == log_on.aux["n_traces"]
    health = log_on.aux["health"]
    assert tuple(health) == ("consensus", "grad_dev")
    for series in health.values():
        assert series.shape == (12,) and np.all(np.isfinite(series))
    assert np.all(health["consensus"] >= 0.0)


# ---------------------------------------------------------------------------
# RetraceGuard
# ---------------------------------------------------------------------------


def test_retrace_guard_counts_exact_jit_compiles():
    guard = RetraceGuard()
    fn = jax.jit(guard.wrap(lambda x: x * 2.0, "double"))
    a = jnp.ones((3,))
    for _ in range(4):
        fn(a)  # one shape -> one trace, cache hits after
    assert guard.count("double") == 1
    fn(jnp.ones((5,)))  # new shape -> exactly one more compile
    assert guard.count("double") == 2

    guard.expect("double", 2)
    assert guard.excess() == 0
    fn(jnp.ones((7,)))
    assert guard.excess() == 1
    guard.record("stream", k=3)  # undeclared: counts, never excess
    assert guard.total() == 6 and guard.excess() == 1
    snap = guard.snapshot()
    assert snap == {
        "counts": {"double": 3, "stream": 3},
        "expected": {"double": 2},
        "total": 6,
        "excess": 1,
    }


# ---------------------------------------------------------------------------
# RunReport
# ---------------------------------------------------------------------------


def _small_report():
    logger = MetricLogger()
    logger.log(0, loss=1.5)
    logger.log(1, loss=1.2, acc=0.4)
    logger.aux["n_traces"] = 1
    meter = CommMeter(per_step_bytes=10)
    meter.tick(4, delivered_frac=0.5, deferred_frac=0.25)
    tr = Tracer()
    with tr.span("sim.segment", k=4):
        pass
    guard = RetraceGuard()
    guard.record("roll")
    guard.expect("roll", 1)
    rep = (
        RunReport("unit", seed=0, n=np.int64(8))
        .add_metrics(logger)
        .add_comm(meter)
        .add_events("swap", [{"t": 3}])
        .add_health({"consensus": np.array([1.0, 0.5], np.float32)})
        .add_spans(tr)
        .add_retraces(guard)
    )
    return rep


def test_run_report_write_load_roundtrip(tmp_path):
    rep = _small_report()
    paths = rep.write(str(tmp_path))
    doc = load_report(paths["json"])  # load_report validates
    assert doc["schema"] == "repro.run_report/v1"
    assert doc["meta"] == {"seed": 0, "n": 8}  # numpy meta scrubbed to int
    assert doc["health"]["consensus"] == [1.0, 0.5]
    assert doc["comm"]["total_bytes"] == 20
    assert doc["comm"]["deferred_bytes"] == 10
    assert doc["retraces"]["excess"] == 0
    assert doc["spans"]["by_name"]["sim.segment"]["count"] == 1
    assert len(doc["metrics"]["history"]) == 2
    md = open(paths["md"]).read()
    for section in ("## Retraces", "## Communication", "## Health series",
                    "## Spans", "## Events", "## Metrics"):
        assert section in md
    # health is additive across calls (segments append)
    rep.add_health({"consensus": [0.25]})
    assert rep.to_dict()["health"]["consensus"] == [1.0, 0.5, 0.25]


def test_validate_report_failure_modes():
    good = _small_report().to_dict()
    validate_report(good)  # sanity

    def broken(mutate):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        return doc

    cases = [
        ("schema mismatch", lambda d: d.update(schema="v0")),
        ("name", lambda d: d.update(name="")),
        ("meta", lambda d: d.update(meta=[])),
        ("history", lambda d: d["metrics"].update(history="nope")),
        ("events", lambda d: d["events"].update(swap="nope")),
        ("must be a list", lambda d: d["health"].update(consensus=1.0)),
        ("non-finite", lambda d: d["health"].update(consensus=[float("inf")])),
        ("non-neg int", lambda d: d["comm"].update(total_bytes=-1)),
        ("exceeds total", lambda d: d["comm"].update(deferred_bytes=10**9)),
        ("by_name", lambda d: d["spans"].update(by_name=[])),
        ("bad count", lambda d: d["spans"]["by_name"].update(
            {"sim.segment": {"count": 0, "total_s": 0.0}})),
        ("total inconsistent", lambda d: d["retraces"].update(total=99)),
        ("excess inconsistent", lambda d: d["retraces"].update(excess=5)),
    ]
    for pattern, mutate in cases:
        with pytest.raises(ValueError, match=pattern):
            validate_report(broken(mutate))
    with pytest.raises(ValueError, match="must be a dict"):
        validate_report([])


# ---------------------------------------------------------------------------
# Metric satellites: CommMeter rounding, MetricLogger hardening
# ---------------------------------------------------------------------------


def test_comm_meter_deferred_derived_from_delivered():
    # the regression: volume=10, delivered_frac=0.34, deferred_frac=0.33.
    # Two independent truncations gave delivered=int(3.4)=3 but
    # deferred=int(3.3)=3 -- "deferred == delivered" from pure round-off.
    # Deriving deferred from the truncated delivered keeps the subset
    # invariant strict: int(3 * 0.33/0.34) = 2 < 3.
    m = CommMeter(per_step_bytes=10)
    m.tick(1, delivered_frac=0.34, deferred_frac=0.33)
    assert m.total_bytes == 3
    assert m.deferred_bytes == 2
    assert m.dropped_bytes == 7

    # the invariant holds by construction under many fractional fates
    m = CommMeter(per_step_bytes=7)
    rng = np.random.default_rng(0)
    for _ in range(200):
        dlv = float(rng.uniform(0.0, 1.0))
        dfr = float(rng.uniform(0.0, dlv))
        before = (m.total_bytes, m.deferred_bytes)
        m.tick(int(rng.integers(1, 4)), delivered_frac=dlv, deferred_frac=dfr)
        assert m.deferred_bytes - before[1] <= m.total_bytes - before[0]
    assert m.deferred_bytes <= m.total_bytes
    assert m.total_bytes + m.dropped_bytes == m.steps * 7

    # edge cases: nothing delivered means nothing deferred; equal fracs
    # defer exactly the delivered volume
    m = CommMeter(per_step_bytes=5)
    m.tick(2, delivered_frac=0.0, deferred_frac=0.0)
    assert m.total_bytes == 0 and m.deferred_bytes == 0
    m.tick(2, delivered_frac=0.3, deferred_frac=0.3)
    assert m.deferred_bytes == m.total_bytes == 3

    with pytest.raises(ValueError, match="subset of delivered"):
        CommMeter(per_step_bytes=5).tick(1, delivered_frac=0.2, deferred_frac=0.4)
    with pytest.raises(ValueError, match="delivered_frac"):
        CommMeter(per_step_bytes=5).tick(1, delivered_frac=1.5)


def test_metric_logger_csv_and_jsonl_hardening(tmp_path):
    log = MetricLogger()
    log.log(0, loss=1.0)
    log.log(1, loss=float("nan"), acc=0.5)
    log.log(2, acc=0.75)

    csv_path = str(tmp_path / "m.csv")
    log.to_csv(csv_path)
    lines = open(csv_path).read().splitlines()
    assert lines[0] == "acc,loss,step"
    assert lines[1] == ",1.0,0"
    assert lines[2] == "0.5,,1"  # logged NaN -> explicit empty cell
    assert lines[3] == "0.75,,2"  # missing key -> explicit empty cell

    jsonl_path = str(tmp_path / "m.jsonl")
    log.to_jsonl(jsonl_path)
    rows = [json.loads(l) for l in open(jsonl_path)]
    assert rows[0] == {"step": 0, "loss": 1.0}
    assert rows[1] == {"step": 1, "loss": None, "acc": 0.5}  # NaN -> null
    assert rows[2] == {"step": 2, "acc": 0.75}

    # column(): skip-missing default vs aligned-with-nan
    acc = log.column("acc")
    assert np.array_equal(acc, [0.5, 0.75])
    aligned = log.column("acc", aligned=True)
    assert len(aligned) == 3 and np.isnan(aligned[0])
    assert np.array_equal(aligned[1:], [0.5, 0.75])

    with pytest.raises(ValueError, match="empty value array"):
        node_spread(np.zeros((0,)))


# ---------------------------------------------------------------------------
# Mesh trainer probes (forced 8 host devices, subprocess)
# ---------------------------------------------------------------------------


def _run_with_devices(code: str, n_devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_mesh_probes_bitwise_across_hot_swap():
    """The tentpole acceptance on the real mesh trainer: a probes-enabled
    run_segments rollout is BITWISE the probes-off run across a schedule
    hot swap, emits finite per-step health series, and every compile is
    accounted for by the RetraceGuard (excess == 0)."""
    out = _run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.mixing import schedule_from_matrix, schedule_to_arrays
        from repro.obs import HealthProbes, RetraceGuard
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")

        # probe validation at setup time: tau_bar is a simulator probe,
        # and probes need the online dsgd step
        for kwargs in ({"mode": "dsgd", "online_w": True,
                        "probes": HealthProbes(tau_bar=True)},
                       {"mode": "fsdp", "probes": HealthProbes()},
                       {"mode": "dsgd", "online_w": False,
                        "probes": HealthProbes()}):
            try:
                make_train_setup(cfg, mesh, lr=1e-2, **kwargs)
            except ValueError:
                continue
            raise AssertionError(f"{kwargs} should be rejected")

        guard = RetraceGuard()
        s_off = make_train_setup(cfg, mesh, mode="dsgd", online_w=True, lr=1e-2)
        s_on = make_train_setup(cfg, mesh, mode="dsgd", online_w=True, lr=1e-2,
                                probes=HealthProbes())
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), s_off.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        mix0 = schedule_to_arrays(schedule_from_matrix(T.ring(8)), 4)
        mix1 = schedule_to_arrays(
            schedule_from_matrix(0.5 * T.ring(8) + 0.5 * np.eye(8)), 4)
        hook = lambda t: mix1 if t == 3 else None
        with jax.set_mesh(mesh):
            params = jax.jit(s_off.init_params, out_shardings=sh)(
                jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 8, 2, 32), 0,
                                      cfg.vocab_size)
            batches = {"tokens": toks, "labels": toks}
            r_off = s_off.run_segments(params, None, batches, mix0,
                                       segment_len=2, on_segment=hook,
                                       retrace_guard=guard)
            r_on = s_on.run_segments(params, None, batches, mix0,
                                     segment_len=2, on_segment=hook,
                                     retrace_guard=guard)

        # probe outputs are extra step outputs: the loss trajectory is
        # bit-identical, and the swap landed in both arms
        assert np.array_equal(r_off["losses"], r_on["losses"]), (
            np.abs(r_off["losses"] - r_on["losses"]).max())
        assert r_off["swaps"] == r_on["swaps"] == [3]
        assert r_off["n_traces"] == 1 and r_on["n_traces"] == 1
        assert "health" not in r_off
        health = r_on["health"]
        assert tuple(health) == ("consensus", "grad_dev")
        for name, series in health.items():
            assert series.shape == (8,), (name, series.shape)
            assert np.all(np.isfinite(series)) and np.all(series >= 0), name

        # every compile accounted for: one multi-step trace per setup,
        # the hot swap adds none
        guard.expect("run_segments.multi_step", 2)
        assert guard.count("run_segments.multi_step") == 2, guard.snapshot()
        assert guard.excess() == 0, guard.snapshot()
        print("MESH_PROBES_OK")
    """)
    assert "MESH_PROBES_OK" in out
