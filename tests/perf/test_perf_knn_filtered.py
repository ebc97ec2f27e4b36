"""The files PR 35 adds to the benchmark for `knn-filtered` (big-ann
NeurIPS'23 filtered track, YFCC-10M shapes): the kind
(`perf/kinds/knn-filtered.py`: bags as a CSR pair, eligibility by sorted
posting lists, a reference over the eligible rows alone), the work function
of a filtered scan (`perf/filtered_work.py`), the three readers under
`perf/layers/`, and the configuration's file. Arithmetic on small seeded
data, on a hand-written capture and on hand-written device planes; then the
cell itself through `perf/run.py --cpu-dry-run`, twice and one after the
other (PERF.md sec. 7 row 19d): sound, and with the filter dropped where the
answer is produced."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _perf_dry import REPO, dry_run  # noqa: E402

sys.path.insert(0, str(REPO))

from perf import trace, work  # noqa: E402
from perf.filtered_work import filtered_scan_work  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads((REPO / "perf/configs/knn-filtered.json").read_text())
CELL = "knn-filtered.c32"
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
SIZE = CONFIG["request"]["size"]


def module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, REPO / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer(name: str):
    return module(f"perf/layers/{name}.py", "perf_layer_under_test")


KIND = module("perf/kinds/knn-filtered.py", "perf_kind_knn_filtered")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """The data set at 8,192 rows, as a first run makes and keeps it."""
    home = tmp_path_factory.mktemp("knn_filtered_8192")
    return KIND.dataset(CONFIG, 8192, home, True), home


@pytest.fixture(scope="module")
def drawn(data):
    return KIND.queries(CONFIG, data[0], 2**31 + 35, 0, 256)


# -- the configuration's file and its manifest entries -------------------------


def test_the_configuration_keeps_every_width_of_the_source():
    assert CONFIG["kind"] == "knn-filtered" and CONFIG["dims"] == 192
    assert KIND.VOCAB == 200_386 == CONFIG["source_shapes"]["vocabulary"]
    assert CONFIG["request"] == {"size": 10, "knn": {"k": 10}}
    body = CONFIG["index_body"]
    assert body["settings"] == {"number_of_shards": 1,
                                "number_of_replicas": 0}
    assert body["mappings"]["properties"] == {
        "v": {"type": "knn_vector", "dimension": 192, "space_type": "l2"},
        "tags": {"type": "keyword"}}
    # the one cut, with its reason in the file
    assert CONFIG["reduced"] == ["docs"] == list(CONFIG["cuts"])
    assert CONFIG["docs"] == 1_000_000
    assert CONFIG["source_shapes"]["docs"] == 10_000_000
    assert len(CONFIG["assumed"]) >= 5


def test_the_limits_are_the_tests_filtered_kinds_unweakened():
    theirs = json.loads((REPO / "tests/perf/data/filtered-test.json")
                        .read_text())["limits"]
    mine = dict(CONFIG["limits"])
    # the one number more: what the roofline's reader takes from the kind
    assert mine.pop("eligible_rows_mean") == {"min": SIZE}
    assert mine == theirs
    assert mine["score_gap"] == mine["rank_gap"] == {"max": 0.001}
    assert "recall_at_10" not in mine      # exact: no floor under 1.0


def test_the_manifest_gains_one_configuration_one_cell_three_metrics():
    conf = MANIFEST["configs"][-1]
    assert conf["name"] == "knn-filtered" and conf["reduced"] == ["docs"]
    cell = MANIFEST["workloads"][-1]
    assert cell == {"name": CELL, "config": "knn-filtered", "traffic": "c32",
                    "chips": 1, "why": cell["why"]}
    added = MANIFEST["per_layer"][-3:]
    assert [m["name"] for m in added] == [
        "filter.mask_ms", "filter.device_share", "filtered_scan_roofline"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "qps"
               for m in added)
    assert [m["source"] for m in added] == [
        "program_span", "device_trace", "device_trace"]
    # nothing else names the cell. The metrics without a list follow it;
    # the ten that PR 33's tree cannot read in it (no launch in the
    # batcher's counters, no request whole in a capture: the driver runs
    # the traced cell on the parent too) list the three cells they had
    assert sum(CELL in m.get("workloads", ()) for m in
               MANIFEST["end_to_end"] + MANIFEST["per_layer"]) == 3
    reported = [m["name"] for m in MANIFEST["per_layer"]
                if CELL in m.get("workloads", (CELL,))]
    assert reported == ["device.idle_share", "device.resident_bytes",
                        *(m["name"] for m in added)]
    before = [w["name"] for w in MANIFEST["workloads"][:-1]]
    assert all(m["workloads"] == before for m in MANIFEST["per_layer"]
               if m["name"] in ("batch.mean_merged", "http.pool_wait_ms",
                                "launch.host_pre_ms"))


def test_nothing_under_perf_imports_the_program_or_the_tests():
    for path in ("perf/kinds/knn-filtered.py", "perf/filtered_work.py",
                 "perf/layers/filter.mask_ms.py",
                 "perf/layers/filter.device_share.py",
                 "perf/layers/filtered_scan_roofline.py"):
        text = (REPO / path).read_text()
        assert "opensearch_tpu" not in text
        assert "import tests" not in text and "from tests" not in text


# -- the kind: data set, queries, request, bodies ------------------------------


def test_row_i_is_the_same_at_any_docs(data, tmp_path):
    big, _home = data
    small = KIND.dataset(CONFIG, 4096, tmp_path, True)
    assert np.array_equal(small["corpus"], big["corpus"][:4096])
    assert np.array_equal(small["indptr"], big["indptr"][:4097])
    assert np.array_equal(small["tags"], big["tags"][:small["indptr"][-1]])
    # integers 0-255 in 192 dimensions, as the source's uint8 vectors
    assert big["corpus"].shape == (8192, 192)
    assert big["corpus"].dtype == np.float32
    assert np.array_equal(big["corpus"], np.rint(big["corpus"]))
    assert 0 <= big["corpus"].min() and big["corpus"].max() <= 255


def test_a_later_run_loads_what_the_first_kept(data):
    made, home = data
    assert np.load(home / "corpus.npy").dtype == np.uint8
    again = KIND.dataset(CONFIG, 8192, home, False)
    for name in ("corpus", "indptr", "tags"):
        assert np.array_equal(again[name], made[name])


def test_bags_are_distinct_ascending_and_heavy_tailed(data):
    indptr, tags = data[0]["indptr"], data[0]["tags"]
    sizes = np.diff(indptr)
    assert sizes.min() >= 1 and 9.5 < sizes.mean() < 12.5
    assert sizes.max() > 3 * sizes.mean()
    for row in range(0, 8192, 97):
        bag = tags[indptr[row]:indptr[row + 1]]
        assert np.all(np.diff(bag) > 0)
        assert 0 <= bag[0] and bag[-1] < KIND.VOCAB
    # a skewed law: the commonest word on half the rows, most words on none
    df = np.bincount(tags, minlength=KIND.VOCAB)
    assert df.max() > 0.4 * 8192 and (df == 0).mean() > 0.8


def test_every_drawn_query_leaves_size_rows_or_more_eligible(data, drawn):
    postings = data[0]["postings"]
    assert len(drawn) == 256 and drawn.vectors.shape == (256, 192)
    for i in range(len(drawn)):
        vector, tags = drawn[i]
        assert 1 <= len(tags) <= 2 and len(set(tags)) == len(tags)
        rows = postings.eligible(tags)
        assert len(rows) == drawn.eligible[i] >= SIZE
    pairs = sum(len(t) == 2 for t in drawn.tags)
    assert 0.35 * 256 < pairs < 0.65 * 256      # about half each
    # the same seed draws the same queries, another seed others
    again = KIND.queries(CONFIG, data[0], 2**31 + 35, 0, 256)
    assert again.tags == drawn.tags
    assert np.array_equal(again.vectors, drawn.vectors)
    other = KIND.queries(CONFIG, data[0], 2**31 + 36, 0, 256)
    assert other.tags != drawn.tags
    warm = KIND.queries(CONFIG, data[0], 2**31 + 35, 1, 256)
    assert warm.tags != drawn.tags


def test_csr_eligibility_equals_a_dense_check_at_a_small_vocabulary(
        monkeypatch):
    monkeypatch.setattr(KIND, "VOCAB", 64)
    indptr, tags = KIND._bags(35, 3000)
    postings = KIND.Postings(indptr, tags)
    has = np.zeros((3000, 64), bool)
    has[np.repeat(np.arange(3000), np.diff(indptr)), tags] = True
    for a in range(0, 64, 3):
        assert np.array_equal(postings.eligible((a,)),
                              np.flatnonzero(has[:, a]))
        for b in range(a + 1, 64, 5):
            want = np.flatnonzero(has[:, a] & has[:, b])
            assert np.array_equal(postings.eligible((a, b)), want)
            assert np.array_equal(postings.eligible((b, a)), want)
    rows = np.arange(0, 3000, 7)
    assert np.array_equal(postings.carry(rows, 5), has[rows, 5])


def test_bulk_bodies_carry_every_rows_vector_and_tags(data):
    conf = {**CONFIG, "bulk_docs_per_request": 1000}
    made = data[0]
    bodies = list(KIND.bulk_bodies(conf, made))
    assert [(lo, n) for lo, n, _ in bodies] == [
        (lo, min(1000, 8192 - lo)) for lo in range(0, 8192, 1000)]
    lo, n, body = bodies[3]
    lines = body.decode().split("\n")
    assert lines[-1] == "" and len(lines) == 2 * n + 1
    for i in (0, 1, 499, n - 1):
        row = lo + i
        assert json.loads(lines[2 * i]) == {"index": {"_id": str(row)}}
        doc = json.loads(lines[2 * i + 1])
        assert doc["v"] == made["corpus"][row].astype(int).tolist()
        bag = made["tags"][made["indptr"][row]:made["indptr"][row + 1]]
        assert doc["tags"] == [f"t{t}" for t in bag]


def test_a_request_is_efficient_knn_filtering_over_term_clauses(drawn):
    two = next(i for i in range(len(drawn)) if len(drawn.tags[i]) == 2)
    body = json.loads(KIND.request(CONFIG, drawn[two]))
    assert body["size"] == 10 and list(body["query"]) == ["knn"]
    knn = body["query"]["knn"]["v"]
    assert knn["k"] == 10 and len(knn["vector"]) == 192
    assert knn["filter"] == {"bool": {"filter": [
        {"term": {"tags": f"t{t}"}} for t in drawn.tags[two]]}}


# -- the plain reference and its control ---------------------------------------


@pytest.mark.parametrize("gather_rows", [10**9, 0],
                         ids=["gathered", "one-pass-a-block"])
def test_the_reference_equals_a_two_line_brute_force(
        data, drawn, monkeypatch, gather_rows):
    monkeypatch.setattr(KIND, "GATHER_ROWS", gather_rows)
    made = data[0]
    ref = KIND.reference(CONFIG, made)
    assert ref.docs == 8192
    ids, scores = ref.topk(drawn, SIZE)
    for i in range(len(drawn)):
        vector, tags = drawn[i]
        rows = np.asarray([r for r in range(8192) if all(
            t in made["tags"][made["indptr"][r]:made["indptr"][r + 1]]
            for t in tags)]) if i % 16 == 0 else made[
                "postings"].eligible(tags)
        d2 = ((made["corpus"][rows].astype(np.float64)
               - vector.astype(np.float64)) ** 2).sum(axis=1)
        order = np.lexsort((rows, d2))[:SIZE]
        assert np.array_equal(ids[i], rows[order])
        assert np.allclose(scores[i], 1.0 / (1.0 + d2[order]), rtol=1e-12)
        assert np.allclose(ref.scores(drawn[i], ids[i]), scores[i],
                           rtol=1e-12)


def test_the_reference_waits_on_its_gate_before_each_piece(data, drawn):
    class Gate:
        waits = 0

        def wait(self):
            self.waits += 1

    gate = Gate()
    KIND.reference(CONFIG, data[0]).topk(drawn, SIZE, gate=gate)
    assert gate.waits >= len(drawn)


def test_further_counts_what_the_querys_own_predicate_excludes(data, drawn):
    made = data[0]
    ref = KIND.reference(CONFIG, made)
    ids, scores = ref.topk(drawn, SIZE)
    served = [(q, ids[q], scores[q]) for q in range(0, 64)]
    sound = ref.further(drawn, served)
    assert sound["filter_violations"] == 0
    assert sound["eligible_rows_mean"] == pytest.approx(
        drawn.eligible[:64].mean())
    # a served row that lacks a tag counts once for each tag it lacks
    tags = drawn.tags[0]
    outside = next(r for r in range(8192) if not any(
        t in made["tags"][made["indptr"][r]:made["indptr"][r + 1]]
        for t in tags))
    planted = ids[0].copy()
    planted[3] = outside
    bad = ref.further(drawn, [(0, planted, scores[0]), *served[1:]])
    assert bad["filter_violations"] == len(tags)
    assert ref.further(drawn, []) == {"filter_violations": 0,
                                      "eligible_rows_mean": 0.0}
    assert set(sound) <= set(CONFIG["limits"])


def test_the_control_is_one_precision_down_and_its_scores_show_it(
        data, drawn):
    ref = KIND.reference(CONFIG, data[0])
    ids, scores = ref.control(drawn, SIZE)
    assert KIND.CONTROL == "filtered reference with the query in bfloat16"
    gaps = []
    for i in range(len(drawn)):
        want = ref.scores(drawn[i], ids[i])
        gaps.append(float(np.max(np.abs(scores[i] - want) / want)))
        assert not ref.further(drawn, [(i, ids[i], scores[i])])[
            "filter_violations"]
    assert max(gaps) > 2 * CONFIG["limits"]["score_gap"]["max"]


# -- the work function and the three readers -----------------------------------


def test_the_least_work_of_a_filtered_scan_by_hand():
    ops, moved = filtered_scan_work(1000, 192, 10, 7, 4)
    assert ops == 2 * 7 * 1000 * 192
    assert moved == 7 * (1000 * 192 * 4 + 192 * 4 + 10 * 8)
    # stored as bytes, a quarter of the rows' traffic
    assert filtered_scan_work(1000, 192, 10, 7, 1)[1] == 7 * (
        1000 * 192 + 192 * 4 + 10 * 8)


@pytest.mark.parametrize("eligible", [10, 41_000, 555_754, 1_000_000])
def test_it_is_never_above_the_full_columns_work(eligible):
    ops, moved = filtered_scan_work(eligible, 192, 10, 100, 4)
    full_ops, full_moved = work.exact_scan_work(1_000_000, 192, 10, 100, 100)
    assert ops <= full_ops and moved <= full_moved
    assert (eligible < 1_000_000) == (moved < full_moved)


def planes(scan_ms=1.5, filter_ms=220.0, copy_ms=3.0, requests=12) -> dict:
    """One chip, `requests` filtered requests one after the other: the
    filter executor's programs, the relayout of the column, the scan."""
    events, t = [], 0.0
    for _ in range(requests):
        for name, ms in (("fusion.1 = s32[1048576]", filter_ms),
                         ("copy.2 = f32[1,1048576,192]", copy_ms),
                         ("pallas_knn_fused.1", scan_ms)):
            if ms:
                events.append([name, t, ms * 1e6])
                t += ms * 1e6
        t += 1e6        # a millisecond of idle between requests
    return {"devices": {"/device:TPU:0": events}}


def run_of(reduced: dict, queries: int = 12, seconds: float = 2.7,
           eligible: float | None = 41_000.0):
    return SimpleNamespace(
        config=CONFIG, docs=CONFIG["docs"], peaks=PEAKS,
        cell={"name": CELL, "chips": 1},
        numbers={} if eligible is None else {"eligible_rows_mean": eligible},
        trace=trace.reduce_trace(reduced),
        counters={"trace": ({"t": 1.0}, {"t": 1.0 + seconds})},
        counter_delta=lambda span: {
            "dispatches": queries, "merged_queries": queries,
            "seconds": seconds})


def test_filter_device_share_is_what_is_not_the_scan(tmp_path, monkeypatch):
    share = layer("filter.device_share").not_the_scan_share
    # edges of 0.25 s are cut from both: the middle holds whole requests
    assert share(planes(filter_ms=0.0, copy_ms=0.0, requests=400)
                 ) == pytest.approx(0.0)
    assert share(planes()) == pytest.approx(
        100.0 * (1 - 1.5 / 224.5), rel=2e-2)
    assert share({"devices": {"/device:TPU:0": [
        ["fusion.1", 0.0, 1e9], ["fusion.1", 2e9, 1e9]]}}) is None
    assert share({"devices": {}}) is None
    # through `read`: the reduced trace `run.py` leaves, or nothing
    read = layer("filter.device_share").read
    cell_dir = tmp_path / "trace" / CELL
    cell_dir.mkdir(parents=True)
    (cell_dir / "reduced.json").write_text(json.dumps(planes()))
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir", str(tmp_path)])
    run = run_of(planes())
    assert read(run) == pytest.approx(100.0 * (1 - 1.5 / 224.5), rel=2e-2)
    run.trace = None
    assert read(run) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir",
                                      str(tmp_path / "nowhere")])
    assert read(run_of(planes())) is None


def test_filtered_scan_roofline_by_hand_and_never_above_the_exact_scans():
    read = layer("filtered_scan_roofline").read
    run = run_of(planes())
    _ops, moved = filtered_scan_work(41_000, 192, 10, 12, 4)
    floor_s = moved / PEAKS["bytes_per_s"]      # bandwidth-bound
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    assert read(run) == pytest.approx(
        100.0 * (floor_s / 2.7) / busy_rate, rel=1e-9)
    assert 0 < read(run) < 1.0
    # every row eligible and nothing but a scan at the memory's speed: 100%
    n, per_query_s = CONFIG["docs"], (1_000_000 * 768 + 848) / 819e9
    ideal = run_of(planes(scan_ms=per_query_s * 1e3, filter_ms=0, copy_ms=0),
                   eligible=float(n))
    ideal.trace["busy_s"], ideal.trace["window_s"] = 12 * per_query_s, 2.7
    assert read(ideal) == pytest.approx(100.0, rel=1e-6)
    # nothing to read: no trace, no judged replies, another kind of work
    for broken in (dict(trace=None), dict(numbers={}), dict(peaks=None),
                   dict(config={**CONFIG, "work": {"kind": "exact_scan"}}),
                   dict(counter_delta=lambda span: None)):
        run = run_of(planes())
        vars(run).update(broken)
        assert read(run) is None


def capture_with(mask_ms: list) -> dict:
    """One whole `_search` request a `filter.mask`, well inside the
    capture's steady span; the last one is cut by the closing edge."""
    ms = 1_000_000
    records, t = [], 1_000 * ms
    for i, took in enumerate(mask_ms):
        trace_id, root = f"trace-{i}", f"root-{i}"
        start = t
        records += [
            ["http_request", trace_id, root, None, 1, start,
             start + (took + 4) * ms, {"path": "/knn-filtered/_search"}],
            ["filter.mask", trace_id, f"mask-{i}", f"pre-{i}", 2,
             start + ms, start + (took + 1) * ms,
             {"rows": 1048576, "eligible": 41000, "clauses": 2,
              "upload_bytes": 1048576}],
            ["http.respond", trace_id, f"resp-{i}", root, 1,
             start + (took + 4) * ms, start + (took + 5) * ms, None]]
        t = start + (took + 6) * ms
    fields = ["name", "trace_id", "span_id", "parent_id", "thread",
              "start_ns", "end_ns", "attributes"]
    return {"opened": {"perf_counter_ns": 500 * ms},
            "closed": {"perf_counter_ns": t + 200 * ms},
            "spans": [dict(zip(fields, r)) for r in records]}


def test_filter_mask_ms_is_the_mean_span_of_the_whole_requests():
    read = layer("filter.mask_ms").read
    run = run_of(planes())
    run._host_capture = capture_with([1500.0, 1700.0, 1900.0, 300.0])
    assert read(run) == pytest.approx(1700.0)       # the cut one left out
    # a program without the span (the parent commit): nothing, no error
    run._host_capture = {**run._host_capture, "spans": [
        s for s in run._host_capture["spans"] if s["name"] != "filter.mask"]}
    assert read(run) is None
    run = run_of(planes())
    run.trace = None                                # an untraced run
    assert read(run) is None


# -- the cell through `perf/run.py`: two dry runs, one after the other ---------


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return tmp_path_factory.mktemp("perf_knn_filtered")


def test_the_cell_runs_correct_on_the_cpu_through_the_normal_path(tmp):
    proc, last = dry_run(tmp, CELL, "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is True and last["dry_run"] is True
    assert last["attempted"] > 20 and last["first_fill"] is True
    assert last["metrics"]["recall_at_10"]["value"] == 1.0
    assert set(last["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert list(last["checks"]) == list(CONFIG["limits"])
    assert last["checks"]["filter_violations"]["value"] == 0
    assert last["checks"]["eligible_rows_mean"]["value"] >= SIZE
    # a filtered launch is a dispatch of one query in the batcher's books
    assert last["launches"] >= last["attempted"] - 32
    assert last["queries_per_launch"] == 1.0
    assert last["compiled_in_window"] == 0


def test_the_filter_dropped_where_the_answer_is_made_is_not_correct(tmp):
    proc, last = dry_run(tmp, CELL, "--trace", "0", fault="drop_filter")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is False and last["first_fill"] is False
    assert last["checks"]["filter_violations"]["ok"] is False
    assert last["checks"]["filter_violations"]["value"] > 0
    assert last["checks"]["failed"]["ok"] and last["checks"]["malformed"]["ok"]
    assert last["checks"]["score_gap"]["ok"]
