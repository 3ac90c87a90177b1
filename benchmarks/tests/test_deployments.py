"""What a deployment and a mix may carry since PR 49, on a CPU: the
``riak_ensemble`` settings of a configuration (``trust_lease`` false:
every read a device round, held to 0 reads from the mirror) and YCSB's
inserts with ``latest`` (workload D), through ``run.py --benchmark
testdata/deployments/BENCHMARK.json --rehearse``.  The two test
deployments are named by no entry of the root ``BENCHMARK.json``."""

import argparse
import json
import os
import subprocess
import sys

import pytest

import run as runpy

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DEPLOYMENTS = os.path.join(BENCH, "testdata", "deployments",
                           "BENCHMARK.json")
CELL_B = "ycsb-b.ring2-n3-unleased"
CELL_D = "ycsb-d.ring2-n3-unleased"


def rehearse(cell, *extra, seed=4_900_000_003):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         DEPLOYMENTS, "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-2000:]
    return {x["what"]: x for x in map(json.loads, filter(
        lambda ln: ln.startswith("{"), proc.stdout.splitlines()))}


def compared(by):
    return {c["name"]: c["value"] for c in by["checked"]["compared"]}


def a_run(cell, benchmark=None, control=None):
    return runpy.Run(argparse.Namespace(
        benchmark=benchmark, workload=cell, rehearse=True, rate=0.0,
        set=None, control=control, seed=1))


def test_the_test_deployments_are_named_by_no_accepted_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(DEPLOYMENTS) as f:
        test = json.load(f)
    assert not {w["name"] for w in test["workloads"]} & {
        w["name"] for w in bench["workloads"]}
    for kind, names in (("configs", {w["config"] for w in
                                     test["workloads"]}),
                        ("traffic", {w["traffic"] for w in
                                     test["workloads"]})):
        for name in names:
            assert not os.path.exists(
                os.path.join(BENCH, kind, name + ".json"))
            with open(os.path.join(os.path.dirname(DEPLOYMENTS), kind,
                                   name + ".json")) as f:
                assert json.load(f)["test_only"] is True
    assert "data" not in bench


def test_settings_reach_the_child_only_where_the_key_is_present():
    """An accepted configuration gives the command line it gave before
    there were settings; one with the key gives one more argument."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for cell in cells:
        r = a_run(cell)
        cmd = runpy.Child(r).command()
        assert "riak_ensemble" not in r.cfg and r.settings == {}
        assert cmd[2:] == [
            "--n-ens", str(r.cfg["n_ens"]), "--n-peers",
            str(r.cfg["n_peers"]), "--n-slots", str(r.cfg["n_slots"]),
            "--engine", r.cfg.get("engine", "single"), "--chips",
            str(r.cell["chips"]), "--out", r.out, "--rehearse"]
        assert "insertproportion" not in r.traffic
    r = a_run(CELL_B, benchmark=DEPLOYMENTS, control="leased_read")
    cmd = runpy.Child(r).command()
    assert cmd[-4:] == ["--control", "leased_read", "--riak-ensemble",
                        '{"trust_lease": false}']
    assert r.settings == {"riak_ensemble": {"trust_lease": False}}


@pytest.mark.parametrize("settings,code,said", [
    ('{"trust_leases": false}', 2, "trust_leases"),
    ('{"trust_lease": false, "tick": 0.001, "warm": true}', 2,
     "tick, warm"),
], ids=["misspelt", "a_serve_argument"])
def test_an_unknown_setting_ends_the_child_before_jax(settings, code,
                                                      said):
    """Only ``config.Config``'s own fields are settings: nothing else of
    ``svcnode.serve``'s signature becomes settable."""
    proc = subprocess.run(
        [sys.executable, "-X", "importtime",
         os.path.join(BENCH, "server.py"), "--n-ens", "2", "--n-peers",
         "3", "--n-slots", "8", "--out", os.devnull, "--rehearse",
         "--riak-ensemble", settings],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == code
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    assert [x["event"] for x in lines] == ["error"]
    assert said in lines[0]["what"]
    assert " jax" not in proc.stderr and "| jax" not in proc.stderr


def test_an_invalid_setting_fails_config_validate():
    """``Config.validate()`` runs: a tick above the lease is refused by
    the program's own check, not by the harness."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "server.py"), "--n-ens", "2",
         "--n-peers", "3", "--n-slots", "8", "--out",
         os.path.join(ROOT, ".bench_out", "test_invalid_setting"),
         "--rehearse", "--riak-ensemble",
         '{"ensemble_tick": 5.0, "lease_duration": 1.0}'],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, 2)
    assert "config invariant violated" in proc.stderr
    assert '"serving"' not in proc.stdout


def test_unleased_ycsb_b_rehearses_correct_with_no_read_from_the_mirror():
    by = rehearse(CELL_B)
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    for line in ("serving", "checked"):
        assert by[line]["riak_ensemble"] == {"trust_lease": False}
    assert compared(by)["leased_reads"] == 0
    g = dict(by["checked"]["guarantees"])
    assert g.pop("tpu") is False and all(g.values())
    assert g["reads_stated_unleased"] is True
    assert by["window"]["reads"] == 1_140 and by["window"]["inserts"] == 0
    layer = by["per_layer"]
    assert layer["read_fastpath_hit_share"]["value"] == 0.0
    # the window's reads rode flushes: far more than its 60 updates
    assert layer["read_fastpath_hit_share"]["samples"] == 1_140
    assert layer["op_residence_read_p50_ms"]["value"] > 0.3


def test_leased_read_control_is_not_correct():
    by = rehearse(CELL_B, "--control", "leased_read")
    assert by["rehearsed"]["correct_but_for_the_device"] is False
    c = compared(by)
    assert c["leased_reads"] > 1_000
    assert c["stale_reads"] == c["lost_writes"] == c["fabricated_reads"] == 0
    assert by["per_layer"]["read_fastpath_hit_share"]["value"] > 90.0


def test_ycsb_d_rehearses_correct_and_inserts_in_order():
    by = rehearse(CELL_D)
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    w = by["window"]
    assert (w["due"], w["reads"], w["inserts"]) == (1_200, 1_140, 60)
    assert w["failed"] == 0
    c = by["checked"]
    assert all(x["value"] == 0 for x in c["compared"])
    # every inserted key is read back as a written key
    assert c["keys_read_back"] > 128
    assert by["read_back"]["keys_written"] >= 60
    assert c["reads_before_insert"] >= w["reads_before_insert"] >= 0


def test_lost_write_control_is_caught_under_inserts():
    by = rehearse(CELL_D, "--control", "lost_write")
    assert by["rehearsed"]["correct_but_for_the_device"] is False
    c = compared(by)
    assert c["lost_writes"] > 0 or c["stale_reads"] > 0
