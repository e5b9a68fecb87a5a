"""Sensor exposition: Prometheus validity (label escaping, TYPE lines),
histogram semantics (bucket monotonicity, +Inf == _count), fleet series
removal, and concurrent recording under the ambient cluster label."""

import re
import threading

from cruise_control_tpu.utils.sensors import (
    DEFAULT_BUCKETS, SensorRegistry, cluster_label, escape_label_value,
)


def _parse_label_value(escaped: str) -> str:
    """Inverse of the exposition escaping (what a Prometheus parser does)."""
    out = []
    i = 0
    while i < len(escaped):
        c = escaped[i]
        if c == "\\" and i + 1 < len(escaped):
            nxt = escaped[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt, nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def test_label_escaping_round_trip():
    nasty = 'quote " backslash \\ newline \n tail'
    r = SensorRegistry()
    r.count("requests", labels={"path": nasty})
    text = r.render()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("kafka_cruisecontrol_requests_total{"))
    # The emitted line must be ONE line (raw newline would split the
    # sample and break the whole scrape).
    m = re.fullmatch(r'kafka_cruisecontrol_requests_total\{path="(.*)"\} '
                     r'1\.0', line)
    assert m, line
    assert _parse_label_value(m.group(1)) == nasty
    assert escape_label_value(nasty) == m.group(1)


def test_type_lines_for_counters_gauges_histograms():
    r = SensorRegistry()
    r.count("c")
    r.gauge("g", 1.0)
    r.observe("h", 0.2)
    text = r.render()
    assert "# TYPE kafka_cruisecontrol_c_total counter" in text
    assert "# TYPE kafka_cruisecontrol_g gauge" in text
    assert "# TYPE kafka_cruisecontrol_h histogram" in text
    # One TYPE line per family even with multiple label sets.
    r.count("c", labels={"x": "1"})
    assert r.render().count("# TYPE kafka_cruisecontrol_c_total") == 1


def test_histogram_buckets_monotone_and_inf_equals_count():
    r = SensorRegistry()
    values = [0.0004, 0.003, 0.003, 0.04, 0.9, 3.0, 100.0, 500.0]
    for v in values:
        r.observe("solve", v)
    text = r.render()
    pat = re.compile(
        r'kafka_cruisecontrol_solve_bucket\{le="([^"]+)"\} (\d+)')
    buckets = [(le, int(n)) for le, n in pat.findall(text)]
    assert buckets[-1][0] == "+Inf"
    counts = [n for _le, n in buckets]
    assert counts == sorted(counts), "cumulative buckets must be monotone"
    assert counts[-1] == len(values)
    assert f"kafka_cruisecontrol_solve_count {len(values)}" in text
    # every finite bound is parseable and ascending (log-spaced ladder)
    finite = [float(le) for le, _n in buckets[:-1]]
    assert finite == sorted(finite) and finite == list(DEFAULT_BUCKETS)


def test_histogram_quantile_estimates():
    r = SensorRegistry()
    for _ in range(99):
        r.observe("lat", 0.02)
    r.observe("lat", 30.0)
    p50 = r.quantile("lat", 0.50)
    p99 = r.quantile("lat", 0.99)
    assert p50 is not None and 0.01 <= p50 <= 0.025
    assert p99 is not None and p99 <= 0.025, \
        "p99 of 99x20ms + 1x30s still lands in the 25ms bucket"
    assert r.quantile("lat", 1.0) >= 25.0
    assert r.quantile("absent", 0.5) is None


def test_remove_labeled_drops_histogram_series():
    r = SensorRegistry()
    r.observe("span", 0.1, labels={"cluster": "a"})
    r.observe("span", 0.1, labels={"cluster": "b"})
    r.count("jobs", labels={"cluster": "a"})
    removed = r.remove_labeled("cluster", "a")
    assert removed == 2
    text = r.render()
    assert 'cluster="a"' not in text
    assert 'kafka_cruisecontrol_span_bucket{cluster="b"' in text


def test_concurrent_recording_under_cluster_label():
    r = SensorRegistry()
    n = 500
    errs = []

    def work(cid):
        try:
            with cluster_label(cid):
                for _ in range(n):
                    r.count("ops")
                    r.observe("lat", 0.01)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=work, args=(c,)) for c in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    # ContextVar scoping: each thread's records carry ITS cluster label,
    # with no cross-talk and no lost updates under contention.
    for cid in ("a", "b"):
        snap = r.histogram_snapshot("lat", labels={"cluster": cid})
        assert snap["count"] == n
        text = r.render()
        assert f'kafka_cruisecontrol_ops_total{{cluster="{cid}"}} {float(n)}' \
            in text


def test_clear_covers_histograms():
    r = SensorRegistry()
    r.observe("h", 0.5)
    r.clear()
    assert r.histogram_snapshot("h") is None
    assert "bucket" not in r.render()


def test_bucket_quantile_edge_cases_are_pinned():
    """The SLO engine's latency objectives call this hot: edges answer a
    NUMBER (0.0 / the bucket bound), never None/NaN (round 18)."""
    from cruise_control_tpu.utils.sensors import bucket_quantile
    # Empty window: all-zero counts -> 0.0.
    assert bucket_quantile((0.1, 1.0), [0, 0, 0], 0.99) == 0.0
    # No finite bounds at all -> 0.0.
    assert bucket_quantile((), [5], 0.5) == 0.0
    # Single-bucket layout answers its one bound.
    assert bucket_quantile((2.5,), [3, 1], 0.5) == 2.5
    # +Inf overflow clamps to the top finite bound.
    assert bucket_quantile((0.1, 1.0), [0, 0, 7], 0.99) == 1.0
    # A NaN can never escape: every answer compares equal to itself.
    for counts in ([0, 0, 0], [1, 0, 0], [0, 0, 9]):
        got = bucket_quantile((0.5, 5.0), counts, 0.99)
        assert got == got


def test_registry_quantile_none_only_for_absent_series():
    r = SensorRegistry()
    assert r.quantile("never_observed", 0.5) is None
    r.observe("lat", 0.2, buckets=(0.1, 1.0))
    assert r.quantile("lat", 0.5) is not None
    # Same name, different labels = a different (absent) series.
    assert r.quantile("lat", 0.5, labels={"cluster": "x"}) is None


def test_a_registered_refresh_runs_before_the_render_takes_its_snapshot():
    r = SensorRegistry()
    calls = []

    def refresh():
        calls.append(len(calls))
        r.set_counter("kept_elsewhere", 7 + len(calls),
                      labels={"generation": "2"})
        r.set_timer("pause", 3, 0.5, 0.1, 0.3, labels={"generation": "2"})

    r.add_refresh(refresh)
    r.add_refresh(refresh)      # adding one twice keeps one
    text = r.render()
    assert calls == [0]
    assert 'kafka_cruisecontrol_kept_elsewhere_total{generation="2"} 8.0' \
        in text
    assert 'kafka_cruisecontrol_pause_seconds_count{generation="2"} 3' in text
    assert 'kafka_cruisecontrol_pause_seconds_sum{generation="2"} 0.5' in text
    assert 'kafka_cruisecontrol_pause_seconds_max{generation="2"} 0.3' in text
    # the total is set, not added to
    assert 'kept_elsewhere_total{generation="2"} 9.0' in r.render()
    r.remove_refresh(refresh)
    r.render()
    assert calls == [0, 1]


def test_a_refresh_that_fails_does_not_break_the_render(caplog):
    r = SensorRegistry()
    r.count("requests")
    seen = []

    def broken():
        raise RuntimeError("no backend")

    r.add_refresh(broken)
    r.add_refresh(lambda: seen.append(True))
    with caplog.at_level("WARNING"):
        text = r.render()
    assert "kafka_cruisecontrol_requests_total 1.0" in text
    assert seen == [True]       # the refreshes after it still ran
    assert "no backend" in caplog.text
