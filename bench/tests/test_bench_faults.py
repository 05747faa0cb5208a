"""``correct`` comes out false when the timed path is broken underneath, for
each fault a cell can have, and for each configuration's control: the plain
reference at the precision below the configuration's, in the program's
place. The runs skip the look for a chip and use sizes a test can hold."""
import pytest

from bench_testlib import drive

SORT_FAULTS = {
    # a call that hands back its input: the state left unchanged
    "unchanged": "lambda loop: (lambda x: x)",
    # half of the result left as it came in
    "half": "half",
    # one answer altered where it is produced
    "altered": "altered",
}
SORT_BODY = """
def half(loop):
    def call(x):
        y, n = np.array(loop.program(x)), x.shape[0] // 2
        y[n:] = np.asarray(x)[n:]
        return y
    return call


def altered(loop):
    def call(x):
        y = np.array(loop.program(x))
        y[7] += 1
        return y
    return call
"""

TOPK_BODY = """
from repro.engine import SortService

class Broken(SortService):
    def __init__(self, how):
        super().__init__()
        self.how, self.batches = how, 0

    def _run_group(self, kind, gk, reqs, vals=None, *, ascending=True):
        self.batches += 1
        if self.how == "errors" and self.batches > 4:  # after the warm-up ladder 1, 2, 4, 8
            raise RuntimeError("an error in place of every answer")
        if self.how == "half":  # half of every row left out
            reqs = [r[: len(r) // 2] for r in reqs]
        out = super()._run_group(kind, gk, reqs, vals, ascending=ascending)
        if self.how == "unchanged":
            return [np.arange(len(r), dtype=np.int32) for r in reqs]
        if self.how == "half":
            return out
        out[0] = out[0].copy()
        out[0][[0, 1]] = out[0][[1, 0]]
        return out
"""


@pytest.mark.parametrize("fault", sorted(SORT_FAULTS))
def test_sort_fault_is_not_correct(tmp_path, fault):
    r = drive(tmp_path, "sort.uniform.1chip", SORT_BODY, system=SORT_FAULTS[fault])
    assert r["correct"] is False
    assert r["checks"]["mismatched_keys"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "errors"])
def test_topk_fault_is_not_correct(tmp_path, fault):
    r = drive(tmp_path, "topk.decode.steps", TOPK_BODY,
              system=f"lambda loop: Broken({fault!r})", seconds=1.0)
    assert r["correct"] is False


def test_four_chip_exchange_left_out_is_not_correct(tmp_path):
    body = "import jax\njax.lax.all_to_all = lambda x, *a, **k: x\n"
    r = drive(tmp_path, "sort.zipf.4chip", body, devices=4)
    assert r["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_four_chip_fault_is_not_correct(tmp_path, fault):
    r = drive(tmp_path, "sort.zipf.4chip", SORT_BODY, system=SORT_FAULTS[fault], devices=4)
    assert r["correct"] is False


@pytest.mark.parametrize("workload,devices", [
    ("sort.uniform.1chip", 1), ("topk.decode.steps", 1), ("sort.zipf.4chip", 4),
])
def test_control_is_not_correct(tmp_path, workload, devices):
    r = drive(tmp_path, workload, system=f"control.control_system({workload!r})",
              seconds=1.0, devices=devices)
    assert r["correct"] is False
