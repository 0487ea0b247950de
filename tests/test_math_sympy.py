"""Sympy-grade math verification parity suite.

Mirrors the tricky-pair coverage of the reference's qwen grader
(/root/reference/math_verify_utils_qwen.py): fractions vs decimals vs
radicals, intervals, sets, tuples, matrices, equations — graded through
the process-pool path (`answers_match_sympy`) and the full
`verify_math` pipeline.
"""

import time

import pytest

from areal_tpu.interfaces.math_sympy import (
    answers_match_sympy,
    latex_to_expr,
    sympy_match_worker,
)


MATCH_PAIRS = [
    # fractions / decimals / radicals
    (r"0.5", r"\frac{1}{2}"),
    (r"\dfrac{3}{4}", r"0.75"),
    (r"\frac{\sqrt{2}}{2}", r"\frac{1}{\sqrt{2}}"),
    (r"2\sqrt{3}", r"\sqrt{12}"),
    (r"\sqrt[3]{8}", r"2"),
    (r"\frac{1}{3} + \frac{1}{6}", r"\frac{1}{2}"),
    (r"1\frac{1}{2}", r"\frac{3}{2}"),
    (r"-\frac{7}{2}", r"-3.5"),
    (r"\frac{22}{7}", r"22/7"),
    (r"0.1", r"\frac{1}{10}"),
    # symbolic
    (r"x^2 - 1", r"(x-1)(x+1)"),
    (r"2x + 2", r"2(x+1)"),
    (r"\frac{x^2-4}{x-2}", r"x+2"),
    (r"e^{2\ln 3}", r"9"),
    (r"\cos(0)", r"1"),
    (r"2\pi", r"\pi \cdot 2"),
    (r"\frac{\pi}{4}", r"0.25\pi"),
    # equations
    (r"x = 5", r"5"),
    (r"y = \frac{1}{2}", r"0.5"),
    # percent / formatting noise
    (r"50\%", r"50"),
    (r"1{,}000", r"1000"),
    (r"\left(3\right)", r"3"),
    (r"45^\circ", r"45"),
    # tuples / points
    (r"(1, 2)", r"(1.0, 2.0)"),
    (r"(\frac{1}{2}, \frac{3}{4})", r"(0.5, 0.75)"),
    # intervals
    (r"[0, 1)", r"[0, 1)"),
    (r"(-\infty, 3]", r"(-\infty, 3]"),
    (r"(1,2] \cup [3,4)", r"(1,2] \cup [3,4)"),
    # sets
    (r"\{1, 2, 3\}", r"\{3, 2, 1\}"),
    (r"\{\frac{1}{2}, 2\}", r"\{2, 0.5\}"),
    # matrices
    (
        r"\begin{pmatrix} 1 & \frac{1}{2} \\ 0 & 1 \end{pmatrix}",
        r"\begin{pmatrix} 1 & 0.5 \\ 0 & 1 \end{pmatrix}",
    ),
    (r"\begin{bmatrix} 2 \\ 4 \end{bmatrix}", r"\begin{bmatrix} 2 \\ 4 \end{bmatrix}"),
]

REJECT_PAIRS = [
    (r"0.5", r"\frac{1}{3}"),
    (r"\sqrt{2}", r"2"),
    (r"(1, 2)", r"(2, 1)"),
    (r"[0, 1)", r"[0, 1]"),  # bracket kind differs
    (r"\{1, 2\}", r"\{1, 2, 3\}"),
    (r"x + 1", r"x - 1"),
    (r"\begin{pmatrix} 1 \\ 0 \end{pmatrix}", r"\begin{pmatrix} 0 \\ 1 \end{pmatrix}"),
    (r"2\pi", r"\pi"),
    (r"x = 5", r"4"),
    (r"\frac{22}{7}", r"\pi"),  # close numerically but not equal
]


@pytest.mark.parametrize("pred,gold", MATCH_PAIRS)
def test_equivalent_pairs(pred, gold):
    assert sympy_match_worker(pred, gold), (
        pred, gold, latex_to_expr(pred), latex_to_expr(gold),
    )


@pytest.mark.parametrize("pred,gold", REJECT_PAIRS)
def test_non_equivalent_pairs(pred, gold):
    assert not sympy_match_worker(pred, gold), (
        pred, gold, latex_to_expr(pred), latex_to_expr(gold),
    )


def test_pool_path_and_timeout_recovery():
    # Through the process pool...
    assert answers_match_sympy(r"\frac{1}{2}", "0.5")
    assert not answers_match_sympy("1", "2")
    # ...and a pathological input must come back False within the timeout,
    # after which the pool still serves.
    assert not answers_match_sympy("(" * 2000, "1", timeout=2.0)
    assert answers_match_sympy(r"2\sqrt{3}", r"\sqrt{12}")


def test_a_call_that_fails_late_on_a_killed_worker_spares_its_replacement(
        monkeypatch):
    """The reward service grades a batch on eight threads over the one
    worker: the calls queued behind a hung one fail with it, some of them
    after the next call has started the replacement — which is an
    interpreter and an import of sympy, not a fork, and must not be killed
    for the old one's sake."""
    from areal_tpu.interfaces import math_sympy

    assert answers_match_sympy("1", "1")
    old = math_sympy._executor()
    # A comparison sympy does not come back from (20 s and counting, alone).
    t0 = time.monotonic()
    assert not answers_match_sympy("(x+1)^{3000}", "x+7", timeout=1.0)
    assert time.monotonic() - t0 < 3.0
    assert math_sympy._EXECUTOR is None  # killed, not waited for
    assert answers_match_sympy(r"\frac{1}{2}", "0.5")  # the first call after
    new = math_sympy._EXECUTOR
    assert new is not None and new is not old
    # A call that had taken `old` before the kill fails on it only now.
    monkeypatch.setattr(math_sympy, "_executor", lambda: old)
    assert not answers_match_sympy("1", "1")
    monkeypatch.undo()
    assert math_sympy._EXECUTOR is new
    assert answers_match_sympy(r"2\sqrt{3}", r"\sqrt{12}")


def test_verify_math_uses_sympy_stage():
    from areal_tpu.interfaces.math_verify import verify_math

    # The fast string/Fraction path cannot grade these; the sympy stage must.
    assert verify_math(
        r"... the answer is \boxed{\frac{\sqrt{2}}{2}}",
        [r"\boxed{\frac{1}{\sqrt{2}}}"],
    )
    assert not verify_math(
        r"... the answer is \boxed{\sqrt{2}}", [r"\boxed{2}"]
    )


# ---------------------------------------------------------------------------
# Reference-grader parity table (round 5).
#
# The vectors below are the tricky pairs the reference's verdict-grade
# grader exercises in its self-test
# (/root/reference/evaluation/grader.py:357 `_test_math_equal`) plus the
# qwen pipeline's semantics (math_verify_utils_qwen.py).  Expected values
# are the REFERENCE's verdicts.  Pairs our from-scratch grader does not yet
# decide the same way are xfail-annotated — a documented pass-rate against
# the reference corpus, not silent divergence.
# ---------------------------------------------------------------------------

REFERENCE_VECTORS = [
    # (pred, gold, reference_verdict, xfail-reason-or-None)
    ("0.0833333333333333", r"\frac{1}{12}", True, None),
    ("(1,4.5)", r"(1,\frac{9}{2})", True, None),
    (r"\frac{x}{7}+\frac{2}{7}", r"\frac{x+2}{7}", True, None),
    (r"\sec^2(y)", r"\tan^2(y)+1", True, None),
    (
        r"\begin{pmatrix}-\frac{7}{4}&-2\\4&\frac{1}{4}\end{pmatrix}",
        r"(\begin{pmatrix}-\frac{7}{4}&-2\\4&\frac{1}{4}\\\end{pmatrix})",
        True,
        None,
    ),
    (
        r"\begin{pmatrix}0.290243531202435\\0.196008371385084\\-0.186381278538813\end{pmatrix}",
        r"(\begin{pmatrix}0.29\\0.196\\-0.186\\\end{pmatrix})",
        True,
        "entry 0.290243 vs 0.29 is outside even the reference's 1e-4 "
        "rel-tol (grader.py:278); its vendored latex2sympy path is not "
        "runnable here (no antlr) to confirm its actual verdict — kept "
        "as the one documented divergence",
    ),
    (
        r"\frac{\sqrt{\sqrt{11}+\sqrt{194}}}{2\sqrt{33}+15}",
        r"\frac{\sqrt{\sqrt{11}+\sqrt{194}}}{15+2\sqrt{33}}",
        True,
        None,
    ),
    ("-34x-45y+20z-100=0", "34x+45y-20z+100=0", True, None),
    ("(+5)(b+2)", "(a+5)(b+2)", False, None),
    (r"\frac{1+\sqrt{5}}{2}", "2", False, None),
    ("1", r"1\\sqrt{19}", False, None),
    ("(0.6,2.6667]", r"(\frac{3}{5},\frac{8}{3}]", True, None),
    ("x+1", "x+2n+1", False, None),
]


@pytest.mark.parametrize(
    "pred,gold,want,xfail", REFERENCE_VECTORS,
    ids=[f"v{i}" for i in range(len(REFERENCE_VECTORS))],
)
def test_reference_grader_parity(pred, gold, want, xfail):
    if xfail:
        pytest.xfail(xfail)
    got = answers_match_sympy(pred, gold, timeout=10.0)
    assert got == want, (pred, gold, got, want)


class TestMultipleChoice:
    """GPQA/MMLU-style grading (reference: grader.py:30 choice_answer_clean,
    math_eval.py:369,596)."""

    def test_choice_clean_last_letter_wins(self):
        from areal_tpu.interfaces.math_verify import choice_answer_clean

        assert choice_answer_clean("The answer is (B).") == "B"
        assert choice_answer_clean("A or C? I'll go with D") == "D"
        assert choice_answer_clean("42") == "42"

    def test_verify_math_choice_gold(self):
        from areal_tpu.interfaces.math_verify import verify_math

        assert verify_math(r"thus \boxed{B}", ["B"])
        assert verify_math("The answer is (C).", ["C"])
        assert not verify_math("The answer is (C).", ["B"])
        # Multi-letter gold (select-all-that-apply).
        assert verify_math(r"\boxed{ACD}", ["ACD"])
        assert not verify_math(r"\boxed{AD}", ["ACD"])
        # Prose statements shed stray capitals; standalone letters win.
        assert verify_math("Therefore the answers are A, C and D", ["ACD"])
        assert not verify_math("Therefore the answers are A and D", ["ACD"])

    def test_choice_without_boxed_uses_last_line(self):
        from areal_tpu.interfaces.math_verify import verify_math

        text = "Because A implies B...\nFinal: (E)"
        assert verify_math(text, ["E"])

    def test_numeric_percent_and_reltol(self):
        from areal_tpu.interfaces.math_verify import answers_match

        assert answers_match("0.5", r"50\%")
        assert answers_match("50", "0.5")  # percent-flexible both ways
        assert answers_match("3.14159", "3.141592653589793")
        assert not answers_match("33.3", r"\frac{100}{3}")  # rel 1e-3 > tol


class TestChoiceExtractionRobustness:
    """Round-5 hardening: prose pollution and order-insensitivity."""

    def test_trailing_I_does_not_override(self):
        from areal_tpu.interfaces.math_verify import verify_math

        assert verify_math("The answer is (B). I am confident.", ["B"])
        assert verify_math("Answer: B. I checked twice", ["B"])

    def test_bare_A_and_I_still_gradeable(self):
        from areal_tpu.interfaces.math_verify import verify_math

        assert verify_math("the answer is A", ["A"])
        assert verify_math(r"\boxed{I}", ["I"])
        assert not verify_math("the answer is B", ["A"])

    def test_multi_letter_order_and_duplicates(self):
        from areal_tpu.interfaces.math_verify import verify_math

        assert verify_math("The correct options are (C) and (A).", ["AC"])
        assert verify_math("B and D. B is right because...", ["BD"])
        assert not verify_math("(C) and (A) and (D)", ["AC"])

    def test_positional_scan_last_letter_wins_across_styles(self):
        """POSITIONAL pin: the LAST letter wins whether parenthesized or
        standalone — a paren-beats-standalone priority would grade (A)
        here and misgrade the self-correction."""
        from areal_tpu.interfaces.math_verify import choice_answer_clean

        assert choice_answer_clean("(A) is wrong, the answer is B") == "B"
        assert choice_answer_clean("B is tempting but (C)") == "C"
        # Bare A/I stay weak regardless of position: a strong earlier
        # candidate beats a trailing English-word letter.
        assert choice_answer_clean("The answer is (B). I am sure.") == "B"
        # ...but with no strong candidate anywhere, the weak one counts.
        assert choice_answer_clean("I") == "I"
        assert choice_answer_clean("probably A") == "A"
        # F-J extension (10-option sets the A-E reference would miss).
        assert choice_answer_clean("the answer is (J)") == "J"

    def test_is_multi_choice_row_evidence_gate(self):
        """Row-level evidence decides; gold-string inference is only the
        no-evidence fallback (a math gold of 'C' must not silently grade
        as a choice row when the row says it is not one)."""
        from areal_tpu.interfaces.math_verify import is_multi_choice

        # No evidence: infer from the gold string.
        assert is_multi_choice("B")
        assert is_multi_choice("ACD")
        assert not is_multi_choice("1/2")
        assert not is_multi_choice("")
        # Row says choice: still requires a letters-only gold (a choice
        # row whose gold is the option TEXT grades as a plain answer).
        assert is_multi_choice("B", is_choice=True)
        assert not is_multi_choice("the rain in spain", is_choice=True)
        # Row says NOT choice: letter-shaped math golds stay math.
        assert not is_multi_choice("C", is_choice=False)
        assert not is_multi_choice("AB", is_choice=False)
