"""The benchmark's files against its contract, and its arithmetic against
values worked by hand.  Nothing here touches a device."""

import json
import os
import re
import sys

import pytest

import bench_toy
import contract
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


def cells_of(metric, manifest=MANIFEST):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


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
    contract.check_config(entry, cfg)
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


def check_metric_entry(metric, manifest=MANIFEST, bench=BENCH):
    """One entry of ``end_to_end`` or ``per_layer`` against the contract,
    and its file under ``bench``/metrics: of the manifest here, or of a copy
    that a later PR has appended to."""
    end_to_end = metric in manifest["end_to_end"]
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
    path = readers.metric_file(bench, metric["name"])
    assert path is not None
    assert os.path.basename(path)[:-len(".json")] in (
        metric["name"], metric["name"].rpartition(".")[0])
    on_file = load(path)
    assert set(on_file) <= {"reader", "args", "what"} and on_file["what"]
    sys.path.insert(0, bench)       # a copy's readers stand beside its files
    try:
        assert callable(readers.resolve(on_file["reader"]))
    finally:
        sys.path.remove(bench)


def check_per_layer_moves(metric, manifest=MANIFEST):
    """A per-layer entry moves an end-to-end metric that each of its cells
    reports, and every cell it names exists."""
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == metric["moves"])
    assert set(cells_of(metric, manifest)) <= set(cells_of(moved, manifest))
    assert set(cells_of(metric, manifest)) <= {
        w["name"] for w in manifest["workloads"]}
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_is_the_contracts_and_its_file_names_a_reader(metric):
    check_metric_entry(metric)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_each_of_its_cells_reports(metric):
    check_per_layer_moves(metric)


def test_no_metric_file_waits_without_an_entry():
    """Every file under metrics/ is read by some entry (a retired metric's
    file goes with its entry; a metric that waits beside the manifest is
    one the driver cannot see), and no configuration's file carries how
    often a kernel runs in a step: the readers count that in the trace."""
    read = {os.path.basename(readers.metric_file(BENCH, m["name"]))
            for m in METRICS}
    assert set(os.listdir(os.path.join(BENCH, "metrics"))) == read
    assert not os.path.exists(os.path.join(BENCH, "sdar_per_layer.json"))
    for root, _, names in os.walk(BENCH):
        for f in names:
            if f.endswith(".json"):
                with open(os.path.join(root, f)) as fh:
                    assert "calls_per_step" not in fh.read(), f


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


# -- a configuration's entry and its file; a cut one ------------------------

@pytest.fixture(scope="module")
def toy_manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("contract")))
    return path, load(path)


@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["configs"]]
                         + list(bench_toy.TOY_CONFIGS))
def test_configuration_meets_the_contract(toy_manifest, name):
    """The two configurations that are here (nothing cut) and the toy
    copy's, one of them cut, under the one function."""
    path, manifest = toy_manifest
    entry = next(e for e in manifest["configs"] if e["name"] == name)
    cfg = load(os.path.join(os.path.dirname(path), entry["file"]))
    contract.check_config(entry, cfg)
    if name in ("resnet50", "gpt2-medium"):
        assert entry["reduced"] == [] and "published" not in cfg
    if name == "gpt2-toy":
        assert entry["reduced"] == ["n_layer", "vocab_size"]
        assert cfg["published"] == {"n_layer": 24, "vocab_size": 50257}
        assert (cfg["n_layer"], cfg["vocab_size"]) == (2, 64)


def _cut():
    cfg = bench_toy.TOY_CONFIGS["gpt2-toy"]()
    entry = {"name": cfg["name"], "source": "toy", "why": "toy",
             "file": "benchmark/configs/gpt2-toy.json",
             "reduced": list(cfg["reduced"])}
    return entry, cfg


MALFORMED = {
    "a reduced key the file lacks": lambda e, c: (
        e["reduced"].append("n_experts"), c["reduced"].append("n_experts"),
        c["published"].update(n_experts=128)),
    "a published value equal to the one held": lambda e, c:
        c["published"].update(n_layer=c["n_layer"]),
    "cut and no deployment": lambda e, c: c.pop("deployment"),
    "the two lists differ": lambda e, c: e["reduced"].pop(),
    "cut and no published": lambda e, c: c.pop("published"),
    "a value larger than the source's": lambda e, c:
        c["published"].update(n_layer=1),
    "a width among the reduced": lambda e, c: (
        e["reduced"].append("n_embd"), c["reduced"].append("n_embd"),
        c["published"].update(n_embd=1024)),
    "a deployment of two lines": lambda e, c:
        c.update(deployment="8 chips\n16 experts here"),
}


@pytest.mark.parametrize("case", MALFORMED, ids=lambda c: c.replace(" ", "-"))
def test_malformed_cut_configuration_fails_the_contract(case):
    entry, cfg = _cut()
    contract.check_config(entry, cfg)       # sound before it is broken
    MALFORMED[case](entry, cfg)
    with pytest.raises(contract.ContractError):
        contract.check_config(entry, cfg)


def cell_files_with_manifest_assertions():
    """{test file's module name: its ``manifest_assertions``} for every
    ``test_*.py`` beside this one that has the function: what a cell's test
    file says of ``BENCHMARK.json`` (its cell's entry, the lists that hold
    its name, its own per-layer entries) as a function of a manifest."""
    import importlib
    here = os.path.dirname(os.path.abspath(__file__))
    found = {}
    for f in sorted(os.listdir(here)):
        if f.startswith("test_") and f.endswith(".py"):
            module = importlib.import_module(f[:-len(".py")])
            if hasattr(module, "manifest_assertions"):
                found[module.__name__] = module.manifest_assertions
    return found


def test_a_later_pr_adds_one_of_each_as_new_files(tmp_path):
    """A configuration, a traffic mix with its generator, a per-layer
    metric with its reader and a cell, added to a copy without editing a
    file that is there; then what a model_config PR appends (a cell on
    ``tokens_per_s_per_chip``, its name at the end of that list and of
    every ``.lm`` list, two per-layer entries of its own at the end of
    ``per_layer``), and every cell file's manifest assertions hold on the
    result: a test that pins the order or the length of a list fails here,
    in the PR that writes it, and not in the next configuration's."""
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
    for name, (reader, _, _) in bench_toy.TOY_METRICS.items():
        added = load(os.path.join(copy, "metrics", name + ".json"))
        assert added["reader"] == "toy_readers:" + reader
    # the configuration added is a cut one
    assert cfg["reduced"] and cfg["published"] and cfg["deployment"]
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
    # -- the next configuration's cell and entries, appended ---------------
    accepted = load(os.path.join(REPO, "BENCHMARK.json"))
    grown = bench_toy.later_pr_appends(manifest_path)
    assert grown["workloads"][-1]["name"] == bench_toy.LATER_CELL
    assert [m["name"] for m in grown["per_layer"][-2:]] == list(
        bench_toy.LATER_METRICS)
    # every accepted entry is where it was, and its list starts as it did
    for kind in ("end_to_end", "per_layer"):
        for was, now in zip(accepted[kind], grown[kind]):
            assert {**now, "workloads": None} == {**was, "workloads": None}
            assert cells_of(now, grown)[:len(cells_of(was, accepted))] == \
                cells_of(was, accepted)
    rate = next(m for m in grown["end_to_end"]
                if m["name"] == "tokens_per_s_per_chip")
    assert rate["workloads"][-1] == bench_toy.LATER_CELL
    for m in grown["per_layer"]:
        assert (m.get("workloads", [None])[-1] == bench_toy.LATER_CELL) == (
            m["name"].endswith(".lm") or m["name"] in bench_toy.LATER_METRICS
            or m["name"] in bench_toy.LATER_SHARED)
    # the contract holds for every entry of the grown manifest, the runner
    # finds the new cell's files, and its line would hold its own metrics
    for m in grown["end_to_end"] + grown["per_layer"]:
        check_metric_entry(m, grown, copy)
    for m in grown["per_layer"]:
        check_per_layer_moves(m, grown)
    _, cell, cfg, traffic, _, _ = bench_run.load_cell(
        manifest_path, bench_toy.LATER_CELL)
    assert (cell["config"], traffic["seq_len"]) == ("gpt2-toy", 128)
    mine = {m["name"] for m in grown["per_layer"]
            if bench_toy.LATER_CELL in cells_of(m, grown)}
    assert set(bench_toy.LATER_METRICS) | {"model.mfu_pct.lm"} <= mine
    assert not {n for n in mine if n.startswith("kernel.")}
    # what each cell's test file says of the manifest holds on the grown one
    said = cell_files_with_manifest_assertions()
    assert {"test_hybrid_cell", "test_sdar_cell",
            "test_flash_bwd_metrics"} <= set(said)
    for module, assertions in said.items():
        assertions(grown)


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


STEP = "jit(train_step)/jit(main)/"


def test_reduction_of_made_up_rows():
    rows = [("/device:TPU:0", "XLA Ops", [
        ("fusion.1", 0.0, 4e9, STEP + "jvp(forward)/block0/dot_general"),
        ("kern_fwd.2", 3e9, 2e9, STEP + "transpose(jvp(forward))/mul"),
        ("fusion.1", 8e9, 1e9, STEP + "jvp(forward)/block0/dot_general"),
        ("copy.3", 9e9, 0.5e9, "")]),
            ("/device:TPU:0", "Steps", [("0", 0.0, 9e9, "")]),
            ("/host:CPU", "python", [("other", 0.0, 9e9, "")])]
    red = xplane.reduce_rows(rows, window_ns=(0.0, 10e9))
    # busy: [0,5) and [8,9.5) = 6.5 s; idle: [5,8) and [9.5,10) = 3.5 s
    assert red["busy_s"] == pytest.approx(6.5) and red["devices"] == 1
    # an operation's seconds stay under its bare name ...
    assert red["op_seconds"] == {"fusion.1": 5.0, "kern_fwd.2": 2.0,
                                 "copy.3": 0.5}
    # ... and so do its events: how often it ran in the window
    assert red["op_events"] == {"fusion.1": 2, "kern_fwd.2": 1, "copy.3": 1}
    assert xplane.op_events(red, "fusion") == 2
    assert xplane.op_events(red, "no such") == 0
    # ... and the busy time is kept by scope path, "" for none: the second
    # [3,4) in which two operations ran goes to the one that started last
    assert red["scope_seconds"] == {
        STEP + "jvp(forward)/block0/dot_general": 4.0,
        STEP + "transpose(jvp(forward))/mul": 2.0, "": 0.5}
    assert sum(red["scope_seconds"].values()) == red["busy_s"]
    # the breakdown names each operation with its phase before it
    assert red["device_ops"] == [["jvp(forward)/fusion.1", 5.0],
                                 ["transpose(jvp(forward))/kern_fwd.2", 2.0],
                                 ["copy.3", 0.5]]
    assert red["idle_gaps"] == [["unannotated", pytest.approx(3.5)]]
    assert xplane.op_seconds(red, "kern_fwd") == (2.0, 1)
    assert xplane.scope_seconds(red, ["forward"], ["transpose("]) == 4.0
    assert xplane.scope_seconds(red, ["block0"]) == 4.0
    assert xplane.scope_seconds(red) == 6.5
    assert not xplane.scopes_missing(red)
    assert xplane.reduce_rows(
        [("/host:CPU", "t", [("x", 0.0, 1.0, "")])]) is None


def test_every_busy_instant_goes_to_the_innermost_operation():
    # a conditional [0,10) holding two operations of its body, [1,4) and
    # [5,9), the second holding a call of its own [6,7); then an operation
    # that outlives the one it started in, [12,15) over [11,13); a gap
    events = [("cond", 0.0, 10.0, ""), ("a", 1.0, 3.0, "x"),
              ("b", 5.0, 4.0, "y"), ("c", 6.0, 1.0, "z"),
              ("d", 11.0, 2.0, "x"), ("e", 12.0, 3.0, "y")]
    own = xplane.self_ns(events)
    assert own == [3.0, 3.0, 3.0, 1.0, 1.0, 3.0]
    busy = sum(e - s for s, e in xplane.merge(
        [s, s + d] for _, s, d, _ in events))
    assert sum(own) == busy == 14.0
    assert sum(d for _, _, d, _ in events) == 23.0   # durations do not
    shuffled = [events[i] for i in (4, 2, 0, 5, 1, 3)]
    assert xplane.self_ns(shuffled) == [own[i] for i in (4, 2, 0, 5, 1, 3)]
    assert xplane.self_ns([]) == []


@pytest.mark.parametrize("scope,want", [
    (STEP + "optimizer/mul", "optimizer"),
    (STEP + "transpose(jvp(forward))/block3/mlp_in/dot_general",
     "transpose(jvp(forward))"),
    ("jit(work)/kern_matmul/dot_general", "kern_matmul"),
    ("jit(train_step)/mul", ""),         # the primitive is no phase
    ("", "")])
def test_phase_is_the_first_scope_that_is_no_jit(scope, want):
    assert xplane.phase(scope) == want


# -- the step split by scope, and the program's counter ---------------------

SPLIT = ("model.forward_ms_per_step", "model.backward_ms_per_step",
         "model.optimizer_ms_per_step", "model.unscoped_pct")


def split_ctx(scope_seconds, busy_s, steps=2, devices=1):
    return {"bench_dir": BENCH, "trace": {
        "steps": steps, "devices": devices, "busy_s": busy_s,
        "scope_seconds": scope_seconds}}


def read(name, ctx):
    m = load(readers.metric_file(BENCH, name))
    return readers.resolve(m["reader"])(ctx, m)


def test_every_operation_falls_in_exactly_one_of_the_four():
    seconds = {
        STEP + "jvp(forward)/block0/dot_general": 0.030,
        STEP + "jvp(forward)/metric/reduce_max": 0.002,
        STEP + "transpose(jvp(forward))/block0/dot_general": 0.050,
        STEP + "optimizer/mul": 0.010,
        STEP + "health/reduce_sum": 0.003,
        STEP + "convert_element_type": 0.001,
        "": 0.004}
    ctx = split_ctx(seconds, busy_s=0.100)
    forward, backward, optimizer, unscoped = (read(n, ctx) for n in SPLIT)
    assert forward == pytest.approx(16.0)       # (30 + 2) ms over 2 steps
    assert backward == pytest.approx(25.0)
    assert optimizer == pytest.approx(5.0)
    assert unscoped == pytest.approx(8.0)       # 3 + 1 + 4 ms of 100
    # the four add up to the busy time: no operation is in two of them
    assert forward + backward + optimizer + unscoped / 100 * 50.0 == \
        pytest.approx(50.0)
    # over two devices the seconds are summed and the busy time is a mean
    both = split_ctx({k: 2 * v for k, v in seconds.items()}, 0.100,
                     devices=2)
    assert read(SPLIT[0], both) == pytest.approx(16.0)
    assert read(SPLIT[3], both) == pytest.approx(8.0)


def test_an_executable_without_scopes_reports_no_split():
    # cached before the program had scopes: 4 of 100 ms carry one
    ctx = split_ctx({"": 0.096, STEP + "optimizer/mul": 0.004}, 0.100)
    assert xplane.scopes_missing(ctx["trace"] | {"devices": 1})
    assert [read(n, ctx) for n in SPLIT[:3]] == [None, None, None]
    assert read(SPLIT[3], ctx) == pytest.approx(96.0)
    # nothing traced (the CPU rehearsal): nothing to read
    nothing = split_ctx({}, 0.0)
    assert [read(n, nothing) for n in SPLIT] == [None] * 4
    assert read(SPLIT[0], {"bench_dir": BENCH, "trace": None}) is None


def test_the_programs_counter_is_read_through_the_job():
    class Mod:
        metric_flushes = {"device": 170, "host": 0}

    class Job:
        mod = Mod()
    name = "loop.metric_device_steps_pct"
    assert read(name, {"job": Job()}) == 100.0
    Mod.metric_flushes = {"device": 150, "host": 50}
    assert read(name + ".lm", {"job": Job()}) == 75.0
    Mod.metric_flushes = {"device": 0, "host": 0}    # no step flushed
    assert read(name, {"job": Job()}) is None
    assert read(name, {"job": object()}) is None     # a job without it


def test_the_traced_window_is_cut_to_whole_steps():
    # the profiler started 60 into a step of 100 and stopped 5 into
    # another: of five executions of the step's program three are whole; a
    # short program (a transfer) between them is not the step's
    step = "jit_train_step(7)"
    modules = [(step, 0.0, 40.0, ""), (step, 40.0, 98.0, ""),
               ("jit_copy(3)", 139.0, 1.0, ""), (step, 140.0, 99.0, ""),
               (step, 240.0, 97.0, ""), (step, 340.0, 5.0, "")]
    ops = [("fusion.1", s, d, STEP + "jvp(forward)/mul")
           for _, s, d, _ in modules]
    rows = [("/device:TPU:0", xplane.MODULES_LINE, modules),
            ("/device:TPU:0", xplane.OPS_LINE, ops)]
    window, steps = xplane.whole_steps(rows)
    assert (window, steps) == ((40.0, 340.0), 3)
    red = xplane.reduce_rows(rows, window_ns=window)
    # the cut executions' operations are outside; the gaps are inside
    assert red["busy_s"] == pytest.approx((98 + 1 + 99 + 97) * 1e-9)
    assert red["window_s"] == pytest.approx(300e-9)
    # over the host's count (it saw four completions) the step would read
    # (40 + 98 + 1 + 99 + 97 + 5) / 4 = 85, not 98.3
    assert red["busy_s"] / steps == pytest.approx(98.33e-9, rel=1e-3)
    # under three executions there is no whole step to cut to
    assert xplane.whole_steps([rows[0][:2] + (modules[:2],)]) is None
    assert xplane.whole_steps([rows[1]]) is None
    # the recorded traces: three calls leave one whole step; two leave none
    want = load(os.path.join(BENCH, "fixtures",
                             "small.expected.json"))["whole_steps"]
    small = xplane.load(FIXTURE)
    window, steps = xplane.whole_steps(small)
    assert (list(window), steps) == (want["window_ns"], want["steps"])
    red = xplane.reduce_rows(small, window_ns=window)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert xplane.op_events(red, "fusion") == want["kernel_events"]
    assert xplane.whole_steps(xplane.load(SCOPED)) is None


def _kernel_step(step_start, kernel_events, other=("fusion.9", 50.0)):
    """One execution of the step's program from ``step_start`` (ns), 1,000
    long: the kernel's events (name, offset, duration) and one other
    operation."""
    ops = [(n, step_start + at, d, STEP + "jvp(forward)/attn/custom_call")
           for n, at, d in kernel_events]
    ops.append((other[0], step_start + 900.0, other[1], STEP + "mul"))
    return ("jit_train_step(7)", step_start, 1000.0, ""), ops


KERNEL_STEPS = {
    # one call a layer, each under a name of its own: two layers
    "a name a layer": ([("kern_bd.1", 0.0, 100.0),
                        ("kern_bd.2", 200.0, 100.0)], 2, 200.0),
    # the block's recomputation runs the forward again: four events
    "recomputed": ([("kern_bd.1", 0.0, 100.0), ("kern_bd.2", 200.0, 100.0),
                    ("kern_bd.3", 400.0, 100.0), ("kern_bd.4", 600.0, 100.0)],
                   4, 400.0),
    # one name under a ``while`` that runs three times a step
    "under a while": ([("kern_bd.1", 0.0, 100.0), ("kern_bd.1", 200.0, 100.0),
                       ("kern_bd.1", 400.0, 100.0)], 3, 300.0),
}


@pytest.mark.parametrize("case", KERNEL_STEPS,
                         ids=lambda c: c.replace(" ", "-"))
def test_a_kernels_calls_a_step_are_its_events_in_the_traced_steps(case,
                                                                   capsys):
    """The roofline share multiplies one call's least time by the events a
    whole step holds, whatever a configuration's file used to say: a list
    of kept values that halves the calls leaves the share where it was."""
    events, calls, ns_per_step = KERNEL_STEPS[case]
    modules, ops = [], []
    for i in range(5):      # the first and the last execution are cut off
        module, step_ops = _kernel_step(2000.0 * i, events)
        modules.append(module)
        ops += step_ops
    rows = [("/device:TPU:0", xplane.MODULES_LINE, modules),
            ("/device:TPU:0", xplane.OPS_LINE, ops)]
    window, steps = xplane.whole_steps(rows)
    red = xplane.reduce_rows(rows, window_ns=window)
    red["steps"] = steps
    assert steps == 3 and xplane.op_events(red, "kern_bd") == 3 * calls
    m = {"args": {"op_name_holds": "kern_bd",
                  "ops_bytes": "opcount:flash_forward_ops_bytes",
                  "shape": {"batch": "batch", "heads": "n_head",
                            "seq": "seq_len", "head_dim": 64, "itemsize": 2}}}
    ctx = {"trace": red, "rehearsal": False, "bench_dir": BENCH,
           "device_kind": "TPU v5 lite", "cfg": {"n_head": 16},
           "traffic": {"batch": 8, "seq_len": 1024}}
    assert readers.kernel_ms_per_step(ctx, m) == pytest.approx(
        ns_per_step * 1e-6)
    ops_one, _ = opcount.flash_forward_ops_bytes(8, 16, 1024, 64, 2)
    # every event took 100 ns: the share is one call's, however many ran
    assert readers.kernel_roofline_pct(ctx, m) == pytest.approx(
        100 * (ops_one / 197e12) / 100e-9)
    assert f"events_per_step={calls} " in capsys.readouterr().out
    # no event of that name: nothing to read, and nothing printed
    none = {**m, "args": {**m["args"], "op_name_holds": "kern_other"}}
    assert readers.kernel_ms_per_step(ctx, none) is None
    assert readers.kernel_roofline_pct(ctx, none) is None
    assert readers.kernel_roofline_pct({**ctx, "trace": None}, m) is None
    assert readers.kernel_roofline_pct({**ctx, "rehearsal": True}, m) is None
    assert capsys.readouterr().out == ""


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
    assert xplane.op_events(red, want["kernel"]) == want["kernel_events"]
    assert red["idle_gaps"][0][1] == pytest.approx(want["idle_s"], rel=1e-9)


# -- the scoped step: a recorded trace with the program's scopes -----------

SCOPED = os.path.join(BENCH, "fixtures", "scoped.xplane.pb")
SCOPED_WANT = load(os.path.join(BENCH, "fixtures", "scoped.expected.json"))


def test_load_keeps_each_operations_scope():
    assert os.path.getsize(SCOPED) < 200 * 1024
    (plane, _, events), = [r for r in xplane.load(SCOPED)
                           if r[0].startswith("/device:")
                           and r[1] == xplane.OPS_LINE]
    assert len(events) == SCOPED_WANT["events"]
    by_name = {}
    for name, _, _, scope in events:
        assert by_name.setdefault(name, scope) == scope
    assert by_name == SCOPED_WANT["scope_of"]
    # read raw from the plane's event metadata, keyed by the whole
    # instruction as the profiler names the event
    raw = xplane.event_scopes(SCOPED)[plane]
    assert {xplane.short_name(k): v for k, v in raw.items()} == {
        k: v for k, v in by_name.items() if v}
    # the older fixture's one scoped operation
    (_, _, small), = [r for r in xplane.load(FIXTURE)
                      if r[0].startswith("/device:")
                      and r[1] == xplane.OPS_LINE]
    assert {e[3] for e in small} == {"", "jit(work)/kern_matmul/dot_general"}


def test_reduction_of_the_scoped_trace_by_scope():
    red = xplane.reduce_rows(xplane.load(SCOPED))
    want = SCOPED_WANT
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["scope_seconds"] == pytest.approx(want["scope_seconds"],
                                                 rel=1e-9)
    assert sum(red["scope_seconds"].values()) == pytest.approx(
        sum(red["op_seconds"].values()), rel=1e-12)
    assert [n for n, _ in red["device_ops"][:len(want["top_ops"])]] == \
        want["top_ops"]
    assert set(red["op_events"].values()) == {want["events_of_each_name"]}
    assert sum(red["op_events"].values()) == want["events"]
    assert {k: xplane.op_events(red, k) for k in want["events_holding"]} \
        == want["events_holding"]
    assert not xplane.scopes_missing(red)


def test_the_scoped_trace_splits_into_exactly_the_four():
    red = xplane.reduce_rows(xplane.load(SCOPED))
    red["steps"] = SCOPED_WANT["steps"]
    ctx = {"bench_dir": BENCH, "trace": red}
    got = {n: read(n, ctx) for n in SPLIT}
    want = SCOPED_WANT["split"]
    assert got == pytest.approx(want, rel=1e-9)
    # no operation overlaps another here, so the four add up to the busy
    # time of a step
    per_step = 1e3 * red["busy_s"] / red["steps"]
    assert sum(got[n] for n in SPLIT[:3]) + got[SPLIT[3]] / 100 * per_step \
        == pytest.approx(per_step, rel=1e-9)
    # a later PR's metric is a data file: any part of the path will do
    sub = {"args": {"holds": ["optimizer/sub"], "lacks": ["forward"]}}
    assert readers.scope_ms_per_step(ctx, sub) == pytest.approx(
        SCOPED_WANT["sub_ms_per_step"], rel=1e-9)
    # the same trace with its scopes gone (an executable from an old cache)
    bare = xplane.reduce_rows([(p, ln, [e[:3] + ("",) for e in ev])
                               for p, ln, ev in xplane.load(SCOPED)])
    bare["steps"] = red["steps"]
    assert xplane.scopes_missing(bare)
    assert [read(n, {"bench_dir": BENCH, "trace": bare})
            for n in SPLIT[:3]] == [None] * 3
    assert read(SPLIT[3], {"bench_dir": BENCH, "trace": bare}) == \
        pytest.approx(100.0)
