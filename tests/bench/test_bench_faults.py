"""A run whose timed path is broken underneath comes out not correct: once
for each fault a serving cell can have.  (A read-only search has no state
to leave unchanged.)"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_bench_rehearsal import bench_run, rehearse, result_of  # noqa: E402

HALF_BATCH = """
from repro.serving import serve
_orig = serve.QueryServer.query_many
def query_many(self, q_idx, q_val, *a, **k):
    q_idx, q_val = np.array(q_idx), np.array(q_val)
    live = int((q_idx >= 0).any(axis=1).sum())
    q_idx[live // 2:live] = -1
    q_val[live // 2:live] = 0.0
    return _orig(self, q_idx, q_val, *a, **k)
serve.QueryServer.query_many = query_many
"""

ALTERED = """
import dataclasses
from repro.serving import serve
_orig = serve.QueryServer.query_many
def query_many(self, *a, **k):
    res = _orig(self, *a, **k)
    ids = np.array(res.ids)
    ids[0, 0] = ids[0, -1]
    return dataclasses.replace(res, ids=ids)
serve.QueryServer.query_many = query_many
"""

@pytest.mark.parametrize("cell,fault", [
    ("splade_f32.open", HALF_BATCH),
    ("g100_f32.open", ALTERED),
], ids=["half_the_batch_left_out", "answer_altered"])
def test_fault_is_not_correct(cell, fault):
    out = bench_run(rehearse(cell, 9), code=fault)
    assert out.returncode == 0, out.stderr[-3000:]
    res = result_of(out)
    assert res["correct"] is False, res["compared"]
