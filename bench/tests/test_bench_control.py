"""The check refuses the control and every fault a search cell can have.

At a tiny size on the CPU: ``control.py`` finds the program's answers
correct, and the reference in its place, with its dense side in int4, not,
nor the faults it plants in the reference's place; and whole runs of the
harness, with the timed path broken underneath, come out not correct.
"""

from __future__ import annotations

import json

import pytest

from tiny_checkout import make_checkout, run_cell, run_child

CELL = "tiny-cfg.tiny-mix"
CLOSED = {"loop": "closed", "clients": 2, "batch": 8, "pool": 1024}

# planted in the program's QueryService, under the harness: each batch's
# answers as the program produced them, then broken
_WRAP = """
import numpy as np
from repro.serve.query_service import QueryService
_orig = QueryService._run_batch
def _broken(self, gen, view, q_dims, q_vals, q_dense, h, alpha, beta,
            span=None):
{body}
QueryService._run_batch = _broken
"""

FAULTS = {
    # a token or an answer altered where it is produced: the first answer
    # of every batch points one row further on
    "answer_altered": _WRAP.format(body="""
    s, i = _orig(self, gen, view, q_dims, q_vals, q_dense, h, alpha, beta,
                 span=span)
    i = i.copy()
    i[0] = i[0] + 1
    return s, i
"""),
    # half of the batch left out: the first half is searched and its
    # answers are handed to the rest as well
    "half_batch_left_out": _WRAP.format(body="""
    k = max(1, q_dims.shape[0] // 2)
    s, i = _orig(self, gen, view, q_dims[:k], q_vals[:k], q_dense[:k], h,
                 alpha, beta, span=span)
    take = np.arange(q_dims.shape[0]) % k
    return s[take], i[take]
"""),
    # rows not scanned: pass 1 sees only the first half of the rows; the
    # later passes re-score what it keeps, so every served score is right
    # for its row
    "rows_not_scanned": """
import jax.numpy as jnp
from repro.core import engine
_bias = engine.pass1_bias
def _half(arrays, q_dims, q_vals, backend=engine.Backend.REF):
    b = _bias(arrays, q_dims, q_vals, backend)
    return jnp.where(jnp.arange(b.shape[1]) < b.shape[1] // 2, b, -jnp.inf)
engine.pass1_bias = _half
""",
    # a wrong selection in the fused pass-1 kernel: it keeps the candidates
    # ranked k+1 to 2k, each with its own pass-1 score, so the later passes
    # refine every served score right for its row
    "wrong_selection": """
from repro.kernels import ops
_topk = ops.lut16_adc_topk
def _second_best(codes, lut, k, **kw):
    s, i = _topk(codes, lut, 2 * k, **kw)
    return s[:, k:], i[:, k:]
ops.lut16_adc_topk = _second_best
""",
}


def test_control_fails_and_program_passes(tmp_path):
    root = make_checkout(tmp_path, CELL, CLOSED)
    proc = run_child(root, ["--workload", CELL, "--seconds", "1",
                            "--seeds", "5,3000000019"], script="control")
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    summary = lines[-1]
    assert summary["seeds"] == 2
    assert summary["program.correct"] == 2, proc.stdout
    assert summary["control.correct"] == 0, proc.stdout
    for fault in ("altered", "rows_half", "wrong_query"):
        assert summary[f"{fault}.correct"] == 0, proc.stdout
    # the faults that re-score their rows exactly are caught by the choice
    # of rows alone
    for fault in ("rows_half", "wrong_query"):
        assert summary[f"{fault}.score_err_rms"][1] < 1e-5, proc.stdout


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tmp_path, fault):
    root = make_checkout(tmp_path, CELL, CLOSED)
    out, err = run_cell(root, CELL, plant=FAULTS[fault])
    assert out["correct"] is False, err[-3000:]
    failing = [k for k, c in out["checks"].items()
               if c["value"] is None or c["value"] > c["limit"]]
    assert failing, out["checks"]
