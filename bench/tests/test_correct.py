"""The correctness comparison fails where it must.

Each test drives a whole run of a cell (source, subscribe, warm-up, window,
comparison) on the CPU at the configuration's rehearsal size, with no chip,
and reads the result line:

* as it stands, the run is correct;
* the configuration's control (the journal without fsync) reads not
  correct;
* each fault the broker's served path can have, planted under the timed
  path, reads not correct: a fire that commits no new replica (state
  unchanged), half of each changeset left out, and the answers altered
  where they are produced (one row of each dropped, or one made up). One chip holds the whole deployment, so no exchange
  between chips can be left out.

    python -m pytest bench/tests
"""
import contextlib
import dataclasses
import io
import json

import numpy as np
import pytest

import run
from repro.core import broker as broker_mod
from repro.core import from_numpy, to_numpy

CELLS = ("location.steady", "location.catchup")


def result(workload, *extra):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse",
                       *extra])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["metrics"] == {}  # a CPU rehearsal reports no metric
    return line


def state_unchanged(monkeypatch):
    monkeypatch.setattr(broker_mod.Broker, "_commit_staged",
                        lambda self, staged: None)


def half_batch(monkeypatch):
    orig = broker_mod.Broker.process_changeset

    def half(self, removed, added):
        return orig(self, removed[: len(removed) // 2],
                    added[: len(added) // 2])

    monkeypatch.setattr(broker_mod.Broker, "process_changeset", half)


def answer_altered(monkeypatch):
    orig = broker_mod.Broker.process_changeset

    def altered(self, removed, added):
        outs = orig(self, removed, added)
        for k, o in enumerate(outs):
            if o is not None:  # one row of each answer dropped or made up
                rows = to_numpy(o.a)
                rows = rows[1:] if len(rows) else np.asarray(
                    removed[:1], np.int32)
                outs[k] = dataclasses.replace(
                    o, a=from_numpy(rows, o.a.capacity))
        return outs

    monkeypatch.setattr(broker_mod.Broker, "process_changeset", altered)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = result(workload)
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    assert not result(workload, "--control")["correct"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    assert not result(workload)["correct"]
