"""CPU tests of the benchmark's harness: lookup by name, generators,
reference counts, the trace reduction, the peaks table and the
refusal to run without a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (BENCH, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from harness import gen, peaks, registry, trace  # noqa: E402
from harness.runner import Window  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_parts_found_by_name(cell):
    c = registry.find_cell(cell)
    assert c.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == cell)
    for mod in (c.driver, c.reference):
        assert callable(getattr(mod, "prepare", None)) or \
            callable(getattr(mod, "expected", None))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "sim_s"}
    assert c.per_layer
    assert set(c.config["limits"]) and c.traffic["job_cap"] > 0


@pytest.mark.parametrize(
    "metric", [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(registry.metric_reader(metric).read)


def test_config_reduced_keys_are_in_the_file():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in cfg and key in cfg["published"]


def test_rmat_is_deterministic_and_distinct_per_job():
    a = gen.rmat_matrix(2**31 + 5, 0, 64, 300)
    b = gen.rmat_matrix(2**31 + 5, 0, 64, 300)
    c = gen.rmat_matrix(2**31 + 5, 1, 64, 300)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0] * 64 + a[1], c[0] * 64 + c[1])
    keys = a[0] * 64 + a[1]
    assert len(np.unique(keys)) == 300 and np.all(np.diff(keys) > 0)
    # R-MAT skew: the heaviest row holds far more than the mean
    assert np.bincount(a[0]).max() > 3 * 300 / 64


def test_kronecker_graph_is_deterministic_undirected_and_distinct():
    g1 = gen.graph500_graph(7, 8, 16)
    g2 = gen.graph500_graph(7, 8, 16)
    g3 = gen.graph500_graph(8, 8, 16)
    assert np.array_equal(g1.src, g2.src) and np.array_equal(g1.dst, g2.dst)
    assert not np.array_equal(g1.src, g3.src) or \
        not np.array_equal(g1.dst, g3.dst)
    fwd = set(zip(g1.src.tolist(), g1.dst.tolist()))
    assert fwd == set(zip(g1.dst.tolist(), g1.src.tolist()))
    assert not np.any(g1.src == g1.dst)
    assert len(fwd) == len(g1.src)


def test_roots_are_seeded_and_follow_the_rule():
    g = gen.graph500_graph(3, 8, 4)
    rule = {"root_rule": "nonzero_degree"}
    r1, r2 = gen.pick_roots(9, g, rule, 20), gen.pick_roots(9, g, rule, 20)
    assert r1 == r2 and len(set(r1)) == 20
    assert np.all(g.out_degree()[r1] > 0)
    assert gen.pick_roots(10, g, rule, 20) != r1


def _dense_bfs(adj, root):
    depth = np.full(len(adj), -1)
    depth[root] = 0
    frontier = [root]
    while frontier:
        nxt = []
        for u in frontier:
            for v in np.flatnonzero(adj[u]):
                if depth[v] < 0:
                    depth[v] = depth[u] + 1
                    nxt.append(v)
        frontier = nxt
    return depth


def test_reference_counts_agree_with_dense_counts():
    spmspm = registry.load_module(BENCH / "reference" / "spmspm.py")
    bfs = registry.load_module(BENCH / "reference" / "bfs.py")
    rows, cols, vals = gen.rmat_matrix(4, 0, 32, 100)
    x = np.zeros((32, 32))
    x[rows, cols] = vals

    class M:
        pass
    job = M()
    job.rows, job.cols, job.vals, job.n = rows, cols, vals, 32
    mults = sum(int(x[k, m] != 0) * int(x[k, n] != 0)
                for k in range(32) for m in range(32) for n in range(32))
    assert spmspm.ops(job) == mults
    ref = spmspm.expected(job, registry.find_cell("gamma-rmat").config)
    z = x.T @ x
    assert len(ref["z"][0]) == np.count_nonzero(z)
    assert ref["stats"]["count mul"] == mults
    assert ref["stats"]["count merge_elem"] == mults
    assert ref["stats"]["count add"] == mults - np.count_nonzero(z)
    assert np.allclose(ref["z"][2], z[ref["z"][0], ref["z"][1]])

    g = gen.graph500_graph(2, 6, 8)
    adj = np.zeros((g.v, g.v), bool)
    adj[g.src, g.dst] = True
    root = int(g.src[0])
    bjob = M()
    bjob.graph, bjob.root, bjob.cap = g, root, 64
    want = _dense_bfs(adj, root)
    got = bfs.expected(bjob, registry.find_cell("bfs-kron").config)
    assert np.array_equal(got["dist"], want)
    assert bfs.ops(bjob) == int(adj[want >= 0].sum())
    assert got["iterations"] == want.max() + 1
    # one multiply per edge traversed, one reduction per edge beyond
    # the first into each destination
    reached = [np.count_nonzero(adj[want == lv].any(axis=0))
               for lv in range(want.max() + 1)]
    assert got["stats"]["count mul"] == 2 * bfs.ops(bjob) - sum(reached)


def test_idle_share_on_a_hand_built_trace():
    device = {"/device:TPU:0": [("fusion", 0, 10), ("intersect", 5, 20),
                                ("fusion", 40, 50), ("late", 95, 130)],
              "/device:TPU:1": []}
    host = [("job 0", 0, 60), ("job 1", 60, 100), ("PjitFunction", 22, 38)]
    lo, hi = trace.job_window(host)
    s = trace.summarize(device, host, (lo, hi))
    assert (lo, hi) == (0, 100) and s.n_devices == 1
    # busy: [0, 20) + [40, 50) + [95, 100) = 35 ns of 100
    assert s.busy_s == pytest.approx(35e-9)
    assert s.device_ops[0] == ["fusion", pytest.approx(20e-9)]
    # longest gap [50, 95) lies in job 0 then job 1; its midpoint 72.5
    # is in job 1; the gap [20, 40) is labelled by the shorter event
    assert s.idle_gaps[0] == ["job 1", pytest.approx(45e-9)]
    assert s.idle_gaps[1] == ["PjitFunction", pytest.approx(20e-9)]
    w = Window(cell="c", setup_s=1.0, timed_s=1.0, job_seconds=[1.0],
               ops=1, peak_rss_bytes=1, base_rss_bytes=0, counters={},
               compiles=0,
               trace=s, trace_window_s=(hi - lo) / 1e9)
    idle = registry.metric_reader("device_idle_share").read(w)
    assert idle == pytest.approx(0.65)


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bfs-kron",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""



def _perfmodel():
    return registry.load_module(BENCH / "reference" / "perfmodel.py")


#: one Einsum "E" whose output Z lives in a 64-byte-line buffer "Buf"
#: (8-byte payloads); A streams from DRAM, T is a fused intermediate
TOY = {"clock_ghz": 1.0, "einsums": ["E"], "blocks": [["E"]],
       "stream": ["T"], "dram": {"name": "DRAM", "gbs": 1.0},
       "components": {"Buf": {"width": 64, "depth": 1024,
                              "instances": 1, "gbs": 2.0},
                      "ALU": {"instances": 2}},
       "bound": {"E": {"Z": "Buf"}}, "compute": {"E": {"mul": "ALU"}},
       "isect": {"name": "Isect", "type": "leader_follower",
                 "leader": "A", "instances": 1},
       "merger": {}, "bytes": {"Z": {"N": [4, 8]}},
       "energy_pj": {"dram_per_byte": 1.0, "sram_small_per_byte": 0.5,
                     "sram_large_per_byte": 2.0, "mul": 1.0, "add": 1.0,
                     "isect_step": 1.0, "merge_elem": 1.0},
       "small_buffer_bytes": 65536}


def test_plain_model_buffer_fills_and_writes_back_once():
    """A buffer line fills on its first touch of each iteration that
    finds it gone, and dirty lines write back at the first evaluation
    only."""
    it = [("einsum", "E", {("touch", "Z", "N", "payload", "r"): 3,
                           ("touch", "Z", "N", "payload", "w"): 5})]
    got = _perfmodel().replay(TOY, [it, it])
    assert got["count sram_read"] == 6 and got["count sram_write"] == 10
    assert got["count sram_fill_bytes"] == 16
    assert got["count sram_drain_bytes"] == 8
    assert (got["dram_read_bytes"], got["dram_write_bytes"]) == (16, 8)
    # bottleneck: buffer bytes (16 accesses x 8 B at 2 GB/s) against
    # DRAM (24 B at 1 GB/s)
    assert got["seconds"] == pytest.approx(128 / 2e9)
    assert got["energy_pj"] == pytest.approx(24 + (128 + 16 + 8) * 0.5)


def test_plain_model_streams_unbound_tensors_and_skips_fused_ones():
    it = [("einsum", "E", {("touch", "A", "K", "coord", "r"): 10,
                           ("touch", "T", "N", "payload", "w"): 7,
                           ("compute", "mul"): 6,
                           ("isect_step", "K", "A"): 9})]
    got = _perfmodel().replay(TOY, [it])
    assert (got["dram_read_bytes"], got["dram_write_bytes"]) == (40, 0)
    assert got["count mul"] == 6 and got["count isect_step"] == 9
    # DRAM 40 B at 1 GB/s outlasts 9 steps and 3 cycles at 1 GHz
    assert got["seconds"] == pytest.approx(40e-9)


def test_stat_gaps_count_a_missing_statistic_whole():
    gaps = _perfmodel().stat_gaps(
        {"count mul": 5.0, "seconds": 1.5},
        {"count mul": 5.0, "count add": 2.0, "seconds": 1.0})
    assert gaps == {"count_gap": 2.0, "model_rel_gap": 0.5}
