"""The control (the reference one precision lower) must come out not
correct, and the reference itself correct, under the harness's comparison,
at a size a test run holds. ``bench/control.py`` runs the same at the
cells' own sizes on the chip."""
import pytest

from ixbench_testkit import ROOT, TINY

from ixbench import compare, graphs  # noqa: E402
from ixbench.harness import _load_json, load_module  # noqa: E402


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
@pytest.mark.parametrize("traffic", ["triangle", "4clique"])
def test_control_is_not_correct(traffic, seed):
    t = _load_json(ROOT / "bench/traffic" / f"{traffic}.json")
    ref = load_module(ROOT / "bench/reference" / f"{t['query']}.py")
    hg = graphs.make_graph(dict(TINY, vertices=1500, m_per_node=20), seed)
    values = ref.values(hg)
    want = compare.exact_answer(values)
    limit = t["answer_gap_limit"]
    assert compare.answer_gap([want], want) <= limit
    assert compare.answer_gap([compare.control_answer(values)],
                              want) > limit
