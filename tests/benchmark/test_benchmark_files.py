"""The benchmark's files against its contract, and its arithmetic against
values worked by hand.  Nothing here touches a device."""

import json
import os
import re
import sys

import pytest

import bench_toy
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import opcount  # noqa: E402
import readers  # noqa: E402
import run as bench_run  # noqa: E402
import xplane  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def cells_of(metric):
    return metric.get("workloads", [w["name"] for w in MANIFEST["workloads"]])


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_resolves_to_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    manifest, c, cfg, traffic, reference, bench_dir = bench_run.load_cell(
        os.path.join(REPO, "BENCHMARK.json"), cell["name"])
    assert os.path.isfile(reference) and bench_dir == BENCH
    assert traffic["steps_per_reading"] >= 1
    module, attr = traffic["generator"].split(":")
    assert callable(getattr(__import__(module), attr))
    entry = next(e for e in MANIFEST["configs"] if e["name"] == c["config"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert cfg["name"] == entry["name"] and cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    module, attr = cfg["driver"].split(":")
    assert os.path.isfile(os.path.join(BENCH, module + ".py"))
    limits = cfg["check"]["limits"]
    assert {"loss_rel", "first_gradient_difference",
            "param_change_worst_leaf"} <= set(limits)
    assert all(v > 0 for v in limits.values())
    assert set(limits) <= set(cfg["check"]["limits_set_from"])
    assert any(cell["name"] in cells_of(m) and m["name"] != "setup_s"
               for m in MANIFEST["end_to_end"])
    assert any(cell["name"] in cells_of(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_is_the_contracts_and_its_file_names_a_reader(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    # what the manifest says the file does not say again; a split name
    # (``.lm``) shares the file of the name before its last dot
    path = readers.metric_file(BENCH, metric["name"])
    assert path is not None
    assert os.path.basename(path)[:-len(".json")] in (
        metric["name"], metric["name"].rpartition(".")[0])
    on_file = load(path)
    assert set(on_file) <= {"reader", "args", "what"} and on_file["what"]
    assert callable(readers.resolve(on_file["reader"]))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_each_of_its_cells_reports(metric):
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"])
    assert set(cells_of(metric)) <= set(cells_of(moved))
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_every_name_is_made_of_the_allowed_characters():
    names = [e["name"] for e in METRICS + MANIFEST["configs"]
             + MANIFEST["workloads"]]
    names += [w[k] for w in MANIFEST["workloads"]
              for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for root, _, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in root:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_a_later_pr_adds_one_of_each_as_new_files(tmp_path):
    """A configuration, a traffic mix with its generator, a per-layer
    metric with its reader and a cell, added to a copy without editing a
    file that is there."""
    manifest_path = bench_toy.make_copy(str(tmp_path))
    before = {f: open(os.path.join(root, f), "rb").read()
              for root, _, files in os.walk(BENCH) for f in files
              if "__pycache__" not in root}
    copy = os.path.join(str(tmp_path), "benchmark")
    for root, _, files in os.walk(copy):
        for f in files:
            if f in before and "__pycache__" not in root:
                assert open(os.path.join(root, f), "rb").read() == before[f]
    manifest, cell, cfg, traffic, reference, bench_dir = bench_run.load_cell(
        manifest_path, "toy-lm")
    assert cfg["name"] == "gpt2-toy" and traffic["batch"] == 2
    assert bench_dir == copy and os.path.isfile(reference)
    added = load(os.path.join(copy, "metrics", "loop.steps_in_window.json"))
    assert added["reader"] == "toy_readers:steps_in_window"
    # the mix's generator is a module the copy added beside traffic.py
    assert traffic["generator"] == "toy_traffic:counting_tokens"
    sys.path.insert(0, copy)
    try:
        import traffic as traffic_lib
        a = traffic_lib.generate(traffic, cfg, 5)
        b = traffic_lib.generate(traffic, cfg, 5)
    finally:
        sys.path.remove(copy)
    assert len(a) == traffic["distinct_batches"]
    assert a[0][0].shape == (2, 128) and (a[0][0] == b[0][0]).all()
    assert (a[0][1][:, :-1] == a[0][0][:, 1:]).all()


# -- operations and bytes, against hand-worked values ---------------------

RESNET50 = load(os.path.join(BENCH, "configs", "resnet50.json"))
GPT2M = load(os.path.join(BENCH, "configs", "gpt2-medium.json"))


def test_resnet50_forward_multiply_adds():
    # stem: 112*112 * 7*7*3*64 = 118,013,952
    # stage 1 (56x56): first block 64->64->64->256 plus the 64->256
    #   shortcut: 3136*(4096+36864+16384+16384) = 231,211,008; two more
    #   blocks 256->64->64->256: 3136*(16384+36864+16384) = 218,365,952 each
    # stage 2..4 and the classifier follow the same rule; the known total
    # of the v1.5 graph is 4,089,184,256 (4.09 G multiply-adds)
    stem = 112 * 112 * 7 * 7 * 3 * 64
    s1 = 3136 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) \
        + 2 * 3136 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    s2 = (3136 * 256 * 128 + 784 * (9 * 128 * 128 + 128 * 512 + 256 * 512)
          + 3 * 784 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
    s3 = (784 * 512 * 256 + 196 * (9 * 256 * 256 + 256 * 1024 + 512 * 1024)
          + 5 * 196 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
    s4 = (196 * 1024 * 512 + 49 * (9 * 512 * 512 + 512 * 2048 + 1024 * 2048)
          + 2 * 49 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
    want = stem + s1 + s2 + s3 + s4 + 2048 * 1000
    assert stem == 118013952 and want == 4089184256
    assert opcount.resnet_forward_macs(RESNET50) == want
    assert opcount.resnet_train_flops_per_item(RESNET50, {}) == 6 * want


def test_gpt2_medium_operations_per_token():
    # a block: 3*1024^2 + 1024^2 + 2*1024*4096 = 12,582,912 weights;
    # 24 blocks 301,989,888; the head 1024*50257 = 51,463,168
    assert opcount.gpt2_matmul_params(GPT2M) == 301989888 + 51463168
    # attention at 1,024: scores and weighted sum, 2*S*d each per token and
    # layer when full, halved by the mask: 24 * 2*1024*1024 = 50,331,648
    want = 6 * 353453056 + 3 * 50331648
    assert opcount.gpt2_train_flops_per_item(GPT2M, {"seq_len": 1024}) == want


def test_flash_forward_operations_and_bytes():
    # 8 x 16 heads, 1,024 x 64: 2 products * 2*1024*1024*64 / 2 per head =
    # 134,217,728; x 128 heads = 17,179,869,184 operations.
    # bytes: 1024 rows * (4 * 64 * 2 + 4) = 528,384 per head; x 128
    ops, nbytes = opcount.flash_forward_ops_bytes(8, 16, 1024, 64, 2)
    assert ops == 17179869184 and nbytes == 67633152
    # on a v5e the bound is compute: 87.2 us against 82.6 us of traffic
    assert ops / 197e12 > nbytes / 819e9


# -- the window's rate, and the median of readings beside it ---------------

def test_rate_is_all_the_work_over_all_the_window():
    # completion times of 8 steps, 2 steps a reading: readings of 1.0, 1.0,
    # 3.0 (a stall), 1.0 seconds
    times = [0.0, 0.5, 1.0, 1.5, 2.0, 4.5, 5.0, 5.5, 6.0]
    stats = bench_run.window_stats(times, 2)
    assert stats["readings"] == [1.0, 1.0, 3.0, 1.0]
    assert stats["steps"] == 8 and stats["window_s"] == 6.0
    assert stats["median_s"] == 1.0
    ctx = {"items_per_step": 3, "chips": 1, "steps_per_reading": 2,
           "median_reading_s": stats["median_s"], **stats}
    # the end-to-end rate carries the stall: 8 steps of 3 items in 6 s;
    # the median reading says 2 steps a second; the stall cost a third
    assert readers.throughput(ctx, {}) == pytest.approx(4.0)
    assert readers.median_reading_rate(ctx, {}) == pytest.approx(6.0)
    assert stats["window_vs_median_pct"] == pytest.approx(100 * (1 - 4 / 6))
    # a step left over after the last whole reading is in the window and
    # in the rate, and in no reading
    more = bench_run.window_stats(times + [6.4], 2)
    assert more["steps"] == 9 and more["window_s"] == 6.4
    assert more["readings"] == stats["readings"]
    none = bench_run.window_stats([0.0], 2)
    assert none["steps"] == 0 and none["median_s"] is None
    assert readers.throughput({**ctx, **none}, {}) is None


def test_slow_steps_say_where_their_time_went():
    import drivers
    clock = drivers.Clock(None, 10.0, 0, 0.0)
    clock.close()
    clock.times = [0.0, 0.1, 0.2, 1.2, 1.3]
    clock.marks = [(0.0, 0.0, 0, 0.0), (0.01, 0.02, 0, 0.0),
                   (0.02, 0.04, 0, 0.0), (0.03, 0.06, 7, 0.5),
                   (0.04, 0.08, 7, 0.5)]
    clock.frozen = [(0.05, 0.3), (0.4, 0.25), (0.6, 0.5)]
    (step, at, wall, thread, process, switches, gc_s, still), = \
        clock.slow_steps()
    assert (step, switches) == (3, 7) and still == pytest.approx(0.75)
    assert at == pytest.approx(0.2) and wall == pytest.approx(1.0)
    assert thread == pytest.approx(0.01) and gc_s == pytest.approx(0.5)


def test_mfu_comes_from_the_traced_steps_busy_time():
    ctx = {"trace": {"steps": 2, "busy_s": 0.5}, "rehearsal": False,
           "bench_dir": BENCH, "device_kind": "TPU v5 lite", "chips": 1,
           "items_per_step": 256, "cfg": RESNET50, "traffic": {}}
    want = 100 * 6 * 4089184256 * 256 * 2 / 0.5 / 197e12
    assert readers.mfu_pct(ctx, {}) == pytest.approx(want)
    assert readers.mfu_pct({**ctx, "trace": None}, {}) is None
    with pytest.raises(KeyError):
        readers.mfu_pct({**ctx, "device_kind": "TPU v9"}, {})


def test_worst_leaf_gap_is_against_the_larger_of_leaf_and_median():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    # leaf c is all but zero: its gap is measured against the median (1.0)
    got = {"a": 1.1, "b": 2.0, "c": 0.05}
    assert bench_run.worst_leaf_gap(got, want) == pytest.approx(0.1)
    assert bench_run.worst_leaf_gap({"a": 1.0}, want) == float("inf")
    # leaves whose gradient is exactly zero do not pull the floor to zero
    want = {"a": 2.0, "b": 0.0, "c": 0.0, "d": 0.0}
    got = {"a": 2.0, "b": 1e-9, "c": 0.0, "d": 0.0}
    assert bench_run.worst_leaf_gap(got, want) == pytest.approx(5e-10)


# -- the trace reduction on the recorded trace -----------------------------

FIXTURE = os.path.join(BENCH, "fixtures", "small.xplane.pb")


def test_reduction_of_made_up_rows():
    rows = [("/device:TPU:0", "XLA Ops", [("fusion.1", 0.0, 4e9),
                                          ("kern_fwd.2", 3e9, 2e9),
                                          ("fusion.1", 8e9, 1e9)]),
            ("/device:TPU:0", "Steps", [("0", 0.0, 9e9)]),
            ("/host:CPU", "python", [("other", 0.0, 9e9)])]
    red = xplane.reduce_rows(rows, window_ns=(0.0, 10e9))
    # busy: [0,5) and [8,9) = 6 s; idle: [5,8) and [9,10) = 4 s
    assert red["busy_s"] == pytest.approx(6.0) and red["devices"] == 1
    assert red["op_seconds"] == {"fusion.1": 5.0, "kern_fwd.2": 2.0}
    assert red["device_ops"][0] == ["fusion.1", 5.0]
    assert red["idle_gaps"] == [["unannotated", pytest.approx(4.0)]]
    assert xplane.op_seconds(red, "kern_fwd") == (2.0, 1)
    assert xplane.reduce_rows([("/host:CPU", "t", [("x", 0.0, 1.0)])]) is None


@pytest.mark.skipif(not os.path.isfile(FIXTURE), reason="no recorded trace")
def test_reduction_of_the_recorded_trace():
    assert os.path.getsize(FIXTURE) < 200 * 1024
    want = load(os.path.join(BENCH, "fixtures", "small.expected.json"))
    red = xplane.reduce_rows(xplane.load(FIXTURE))
    assert red["devices"] == want["devices"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    seconds, names = xplane.op_seconds(red, want["kernel"])
    assert names == want["kernel_names"]
    assert seconds == pytest.approx(want["kernel_s"], rel=1e-9)
    assert red["idle_gaps"][0][1] == pytest.approx(want["idle_s"], rel=1e-9)
