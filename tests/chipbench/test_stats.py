"""Sample arithmetic and the Prometheus readers."""
import os
import statistics

import pytest
from chipbench import DATA_DIR

from harness import readers, stats
from harness.loadgen import Sample


def sample(i, due=None, send=0.0, first=None, last=None, end=None,
           tokens=0, asked=None, finish="length", error=None, phase="window",
           chunks=None):
    s = Sample(i, phase, 10, asked if asked is not None else tokens, due)
    s.send_t, s.first_t, s.last_t, s.end_t = send, first, last, end
    s.tokens, s.finish, s.error = tokens, finish, error
    s.chunks = chunks or ([(first, 1), (last, tokens - 1)]
                          if first is not None and tokens > 1 else [])
    return s


@pytest.mark.parametrize("p,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (100, 5.0), (25, 2.0)])
def test_percentile_interpolates_like_numpy(p, want):
    assert stats.percentile([5, 1, 4, 2, 3], p) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_spread_is_the_contracts():
    vals = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


def test_tpot_is_per_request_over_the_tokens_received_in_the_window():
    w = (10.0, 20.0)
    xs = [sample(0, first=11.0, last=12.0, end=12.0, tokens=11),   # 0.1
          # in flight when the window opened: its part inside counts
          sample(5, first=8.0, last=15.0, end=15.0, tokens=25,
                 chunks=[(8.0, 1), (9.0, 8), (11.0, 8), (15.0, 8)]),  # 0.5
          sample(1, first=10.0, last=16.0, end=16.0, tokens=31),   # 0.2
          # still running when it closed: likewise
          sample(2, first=18.0, last=21.0, end=21.0, tokens=9,
                 chunks=[(18.0, 1), (19.0, 4), (21.0, 4)]),        # 0.25
          sample(6, first=19.0, last=21.0, end=21.0, tokens=5),    # one moment
          sample(3, first=12.0, last=12.0, end=12.0, tokens=1),    # one token
          sample(7, first=12.0, last=14.0, end=None, tokens=9,
                 error="boom"),                                    # failed
          sample(4, first=12.0, last=13.0, end=13.0, tokens=9, phase="probe")]
    assert stats.tpot_values(xs, w) == pytest.approx([0.1, 0.5, 0.2, 0.25])
    assert stats.tpot_values(xs, w, whole_requests=True) == pytest.approx(
        [0.1, 0.2])
    assert stats.client_stat({"of": "tpot_s", "stat": "p50", "scale": 1e3},
                             xs, w, 1, 20.0) == pytest.approx(225.0)


@pytest.mark.parametrize("stall,slowed", [
    ((0.0, 0.0), [False, False, False]),    # no stall
    ((10.5, 2.0), [True, False, True]),     # early: those in flight then
    ((8.0, 1.5), [False, False, False])])   # before the window: none
def test_a_stall_reaches_every_request_in_flight_and_none_before(stall,
                                                                 slowed):
    """Bursts of 8 tokens every 0.8 s (0.1 s a token). Request 0 is in
    flight when the window (10, 20) opens, request 1 begins at 14,
    request 2 at 10.1; a stall holds back every burst due after it."""
    at, length = stall

    def bursts(start, n):
        times = [start + 0.8 * k for k in range(n)]
        return [(t + (length if length and t >= at else 0.0), 8)
                for t in times]

    xs = [sample(0, first=7.0, last=0, end=30.0, tokens=80,
                 chunks=bursts(7.0, 10)),
          sample(1, first=14.0, last=0, end=30.0, tokens=40,
                 chunks=bursts(14.0, 5)),
          sample(2, first=10.1, last=0, end=30.0, tokens=48,
                 chunks=bursts(10.1, 6))]
    got = stats.tpot_values(xs, (10.0, 20.0))
    assert len(got) == 3
    for value, slow in zip(got, slowed):
        if slow:
            assert value > 0.12
        else:
            assert value == pytest.approx(0.1)


def test_tokens_in_window_counts_chunks_by_their_arrival():
    w = (10.0, 20.0)
    xs = [sample(0, first=9.5, last=10.5, end=10.5, tokens=9,
                 chunks=[(9.5, 1), (10.5, 8)]),
          sample(1, first=19.0, last=20.5, end=20.5, tokens=9,
                 chunks=[(19.0, 1), (20.5, 8)])]
    assert stats.tokens_in_window(xs, w) == 9
    assert stats.client_stat({"of": "out_tokens", "stat": "rate_per_chip"},
                             xs, w, 1, 20.0) == pytest.approx(0.9)
    assert stats.client_stat({"of": "out_tokens", "stat": "rate_per_chip"},
                             xs, w, 4, 20.0) == pytest.approx(0.225)


def test_open_loop_misses_count_with_their_wait_and_as_failed():
    w = (0.0, 10.0)
    xs = [sample(0, due=1.0, send=1.0, first=1.5, last=2, end=2, tokens=4),
          sample(1, due=2.0, send=2.01, error="http 500"),
          sample(2, due=9.0, send=9.0),                    # no token yet
          sample(3, due=11.0, send=11.0, first=11.2, last=12, end=12,
                 tokens=4),                                # due after
          sample(4, due=3.0, send=3.0, first=3.1, last=4, end=4, tokens=3,
                 asked=4)]                                 # short answer
    vals = stats.ttft_values(xs, w, cutoff_t=12.0)
    assert vals == pytest.approx([0.5, 10.0, 3.0, 0.1])
    assert stats.attempted_failed(xs, w, "open", 12.0) == (4, 3)
    assert stats.late_values(xs, w) == pytest.approx([0, 0.01, 0, 0])
    assert stats.client_stat({"of": "ttft_s", "stat": "max"}, xs, w, 1,
                             12.0) == pytest.approx(10.0)


def test_closed_loop_attempted_and_failed():
    w = (0.0, 10.0)
    xs = [sample(0, send=0.5, first=1, last=2, end=2, tokens=4),
          sample(1, send=1.0, error="boom"),
          sample(2, send=3.0, first=4, last=5, end=5, tokens=4,
                 finish="stop"),
          sample(3, send=9.0, first=9.5, last=11, end=11, tokens=4)]
    assert stats.attempted_failed(xs, w, "closed", 10.0) == (3, 2)
    assert len(stats.finished_in_window(xs, w)) == 2


@pytest.mark.parametrize("params", [{"of": "ttft_s", "stat": "p95"},
                                    {"of": "late_s", "stat": "mean"},
                                    {"of": "tpot_s", "stat": "p99"}])
def test_client_stat_with_nothing_to_read_returns_nothing(params):
    assert stats.client_stat(params, [], (0.0, 1.0), 1, 1.0) is None


def test_client_stat_refuses_what_it_does_not_know():
    with pytest.raises(ValueError):
        stats.client_stat({"of": "tpot_s", "stat": "median"},
                          [sample(0, first=1, last=2, end=2, tokens=3)],
                          (0, 5), 1, 5)
    with pytest.raises(ValueError):
        stats.client_stat({"of": "e2e_s", "stat": "p50"}, [], (0, 5), 1, 5)


# --- readers on a recorded /metrics text -------------------------------------

def _ctx():
    with open(os.path.join(DATA_DIR, "metrics_start.txt")) as f:
        start = readers.parse_prom(f.read())
    with open(os.path.join(DATA_DIR, "metrics_end.txt")) as f:
        end = readers.parse_prom(f.read())
    return {"prom_start": start, "prom_end": end,
            "detail_end": {"device_telemetry": {"devices": {
                "tpu:0": {"peak_bytes": 14e9}, "tpu:1": {"peak_bytes": 15e9},
                "cpu:0": {"peak_bytes": None}}}}}


def test_parse_prom_reads_labels_and_skips_comments():
    prom = _ctx()["prom_end"]
    assert ({"kind": "real", "phase": "decode"}, 4048.0) in \
        prom["intellillm_tokens_total"]
    assert "HELP" not in "".join(prom)
    name = "intellillm_step_phase_seconds_sum"
    assert any(l["phase"] == "execute" and "model_name" in l
               for l, _ in prom[name])


def test_prom_delta_sums_the_series_it_selects():
    ctx = _ctx()
    compiles = {"reader": "prom_delta",
                "series": {"name": "intellillm_xla_compiles_total"}}
    assert readers.read_metric(compiles, ctx) == 2.0    # 1 mixed + 1 new
    one = dict(compiles, series={"name": "intellillm_xla_compiles_total",
                                 "labels": {"program": "mixed"}})
    assert readers.read_metric(one, ctx) == 1.0
    absent = {"reader": "prom_delta", "absent": 0.0,
              "series": {"name": "intellillm_request_preemptions_total"}}
    assert readers.read_metric(absent, ctx) == 0.0
    assert readers.read_metric(dict(absent, absent=None), ctx) is None


def test_prom_ratio_with_negated_labels_and_summed_denominator():
    ctx = _ctx()
    host = {"reader": "prom_ratio", "scale": 1000.0,
            "numerator": {"name": "intellillm_step_phase_seconds_sum",
                          "labels": {"phase": {"not": "execute"}}},
            "denominator": {"name": "intellillm_step_time_seconds_count"}}
    # (0.3-0.1) + (0.5-0.1) + (2.5-0.5) + (0.25-0.05) = 2.8 s over 100 steps
    assert readers.read_metric(host, ctx) == pytest.approx(28.0)
    pad = {"reader": "prom_ratio", "scale": 100.0,
           "numerator": {"name": "intellillm_tokens_total",
                         "labels": {"kind": "pad"}},
           "denominator": [{"name": "intellillm_tokens_total",
                            "labels": {"kind": "pad"}},
                           {"name": "intellillm_tokens_total",
                            "labels": {"kind": "real"}}]}
    # pad 100+900, real 1000+4000  ->  1000 / 6000
    assert readers.read_metric(pad, ctx) == pytest.approx(100 / 6)
    none = dict(host, denominator={"name": "no_such_series"})
    assert readers.read_metric(none, ctx) is None


def test_prom_hist_quantile_on_the_window_delta():
    ctx = _ctx()
    q = {"reader": "prom_hist_quantile", "quantile": 0.95, "scale": 1000.0,
         "name": "intellillm_request_queue_time_seconds"}
    # window delta: 60 under 0.01, 30 more under 0.05, 10 more under 0.1
    # rank 95 lies in (0.05, 0.1]: 0.05 + 0.05 * (95-90)/10
    assert readers.read_metric(q, ctx) == pytest.approx(75.0)
    assert readers.read_metric(dict(q, quantile=0.5), ctx) == \
        pytest.approx(1000 * 0.01 * 50 / 60)
    assert readers.read_metric(dict(q, name="no_such_histogram"), ctx) is None


@pytest.mark.parametrize("buckets,q,want", [
    ([(1.0, 0), (2.0, 10), (float("inf"), 10)], 0.5, 1.5),
    ([(1.0, 5), (float("inf"), 10)], 0.9, 1.0),     # in +Inf: its lower edge
    ([(1.0, 0), (float("inf"), 0)], 0.5, None)])
def test_hist_quantile_edges(buckets, q, want):
    got = readers.hist_quantile(buckets, q)
    assert got == want if want is None else got == pytest.approx(want)


def test_health_json_walks_wildcards():
    ctx = _ctx()
    m = {"reader": "health_json", "reduce": "max", "scale": 1e-9,
         "path": "device_telemetry.devices.*.peak_bytes"}
    assert readers.read_metric(m, ctx) == pytest.approx(15.0)
    assert readers.read_metric(dict(m, path="device_telemetry.nothing"),
                               ctx) is None


def test_trace_readers_return_nothing_without_a_trace():
    assert readers.read_metric({"reader": "trace_idle"}, {"trace": None}) \
        is None
    assert readers.read_metric({"reader": "trace_op_share", "regex": "x"},
                               {"trace": None}) is None


def test_unknown_reader_is_an_error():
    with pytest.raises(KeyError):
        readers.read_metric({"name": "x", "reader": "tea_leaves"}, {})
