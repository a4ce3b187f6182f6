"""The comparison refuses what it must: the program's own bfloat16 path
(the control), and a timed path broken underneath the harness (an
answer altered where it is produced)."""

import json

import pytest

import _load

CELLS = ("kleinberg_s128.select", "delaunay_n16.drag", "delaunay_n16.serve")


def result(capsys, cell, *extra):
    rc = _load.harness().main(["--workload", cell, "--seed", "424242",
                               "--seconds", "1", "--trace", "0",
                               "--rehearsal", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_is_not_correct(capsys, cell):
    res = result(capsys, cell, "--control")
    assert res["correct"] is False
    over = [k for k, v in res["check"].items() if v["value"] > v["limit"]]
    assert over, res["check"]


def _altered(fn):
    """``fn`` with its N_c and E_c answers doubled (plus one, for a
    layout with none) where the program produces them."""
    def alter(res):
        return res._replace(node_occlusion=2 * res.node_occlusion + 1,
                            edge_crossing=2 * res.edge_crossing + 1)

    def wrapped(*args, **kwargs):
        out = fn(*args, **kwargs)
        if hasattr(out, "_replace"):          # the scores themselves
            return alter(out)
        res, *rest = out                      # (scores, new state)
        return (alter(res), *rest)
    return wrapped


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, cell):
    from repro.core import engine, incremental

    for name in ("evaluate_layouts", "evaluate_planned"):
        monkeypatch.setattr(engine, name, _altered(getattr(engine, name)))
    monkeypatch.setattr(incremental, "evaluate_delta",
                        _altered(incremental.evaluate_delta))
    res = result(capsys, cell)
    assert res["correct"] is False
    assert res["check"]["n_c_gap"]["value"] > res["check"]["n_c_gap"]["limit"]
