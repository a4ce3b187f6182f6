"""Each cell end to end on the CPU at its rehearsal size, and the refusal
of the CPU without the rehearsal flag."""

import json

import pytest

import _load

CELLS = ("kleinberg_s128.select", "delaunay_n16.select",
         "delaunay_n16.drag", "delaunay_n16.serve")


def run_cell(capsys, *argv):
    rc = _load.harness().main(list(argv))
    out = capsys.readouterr()
    return rc, out.out.strip().splitlines(), out.err


def test_the_cpu_is_refused_without_the_rehearsal_flag(capsys):
    rc, out, err = run_cell(capsys, "--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0")
    assert rc != 0 and out == []
    assert "refusing to run" in err


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_is_correct(capsys, cell):
    run = _load.harness()
    spec = run.load_spec()
    rc, out, err = run_cell(capsys, "--workload", cell, "--seed",
                            str(2**31 + 11), "--seconds", "1", "--trace",
                            "1", "--rehearsal")
    assert rc == 0
    res = json.loads(out[-1])
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 1 and "kind" in res["device"]
    assert res["device"]["busy_s"] > 0
    assert 0 < res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    want = {m["name"] for m in run.metrics_for(spec["per_layer"], cell)}
    assert set(res["metrics"]) == want
    assert err.strip().splitlines()[-1].startswith("check failed ")

    rc, out, _ = run_cell(capsys, "--workload", cell, "--seed", "12",
                          "--seconds", "1", "--trace", "0", "--rehearsal")
    res = json.loads(out[-1])
    want = {m["name"] for m in run.metrics_for(spec["end_to_end"], cell)}
    assert set(res["metrics"]) == want and "breakdown" not in res
    assert res["metrics"]["setup_s"]["value"] > 0
