"""What decides ``correct``: the window's scored configurations against the
plain reference that the cell's configuration names (its ``"reference"``,
used only through the contract at the top of ``reference.py``).

After the window has closed, a sample of the configurations it scored,
drawn from the seed and holding the one that trained longest (and, with
several executors, one of each executor's), is checked:

- ``split_gap`` (GBDT, forest): each tree of the model the window produced
  is replayed on the training rows; at every node, how far the split it
  took falls short of the reference's best split, relative to the node;
- ``leaf_gap`` (GBDT, forest): how far its leaf values lie from the
  reference's values for the rows that reach each leaf;
- ``change_gap`` (MLP, logistic regression): the reference trains the same
  configuration on the same draws; per weight or bias array, the gap between
  the norm of the program's change from the initial state and the norm of
  the reference's, relative to the larger of that reference norm and the
  median array's. A state left unchanged reads 1;
- ``loss_gap`` (MLP, logistic regression): how far the objective the family
  minimises, on every training row, at the program's model lies from its
  value at the reference's model, relative to the latter;
- ``score_gap`` (GBDT, forest): how far the validation score the search
  reported, by the configuration's ``metric``, lies from the reference's
  score of the same model by that metric. Tree routing is exact, so an AUC
  agrees to the last digit. A dense model's score is not compared: its
  margins round differently in every matmul lowering, which moves the ranks
  of a small validation set as far as the control does.

Each number is the largest over the sample, and is held to its limit in the
configuration file (``correct_limits``); a configuration compares only the
numbers it gives a limit. The control is the reference
computed in bfloat16, put in the program's place (:func:`control_answers`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import numpy as np

NUMBERS = ("split_gap", "leaf_gap", "score_gap", "change_gap", "loss_gap")
TREES = ("gbdt", "forest")
#: what a number reads where it cannot be computed (a JSON-safe infinity)
UNREADABLE = 1e30


@dataclasses.dataclass
class Answer:
    """One scored configuration: what the search (or the control) returned."""
    family: str
    params: dict
    model: dict | None
    score: float | None
    train_seconds: float = 0.0
    executor: int = 0


def sample(answers: Sequence[Answer], per_family: Mapping[str, int],
           seed: int, n_executors: int = 1) -> list[int]:
    """Indices of the answers to check: per family a draw of the given size,
    plus the longest-training answer, plus one of each executor's."""
    rng = np.random.default_rng(seed)
    chosen: set[int] = set()
    if answers:
        chosen.add(max(range(len(answers)),
                       key=lambda i: answers[i].train_seconds))
    for fam, k in sorted(per_family.items()):
        idx = [i for i, a in enumerate(answers) if a.family == fam]
        if idx:
            chosen.update(int(i) for i in rng.choice(idx, min(k, len(idx)),
                                                     replace=False))
    if n_executors > 1:
        for e in range(n_executors):
            idx = [i for i, a in enumerate(answers) if a.executor == e]
            if idx and not chosen.intersection(idx):
                chosen.add(int(rng.choice(idx)))
    return sorted(chosen)


def _finite(v: float) -> float:
    return float(v) if math.isfinite(v) else UNREADABLE


def numbers(prep, x_valid, y_valid, answer: Answer, ref,
            metric: str) -> dict[str, float]:
    """This answer's numbers against the float32 reference ``ref``, its
    score by ``metric``."""
    if answer.model is None or answer.score is None or not math.isfinite(answer.score):
        return {"score_gap": UNREADABLE}
    out: dict[str, float] = {}
    if answer.family in TREES:
        out.update(ref.replay_trees(prep, answer.family, answer.params,
                                    answer.model))
        got = ref.score(metric, y_valid,
                        ref.probs(answer.family, answer.model, x_valid))
        out["score_gap"] = abs(float(answer.score) - got)
    else:
        import jax.numpy as jnp

        want = ref.fit_dense(prep, answer.family, answer.params, jnp.float32)
        init = ref.dense_init(prep, answer.family, answer.params)
        out["change_gap"] = ref.change_gap(answer.model, want, init)
        best = ref.dense_loss(prep, answer.family, answer.params, want)
        got = ref.dense_loss(prep, answer.family, answer.params, answer.model)
        out["loss_gap"] = abs(got - best) / best
    return {k: _finite(v) for k, v in out.items()}


def control_answers(prep, x_valid, y_valid, answers: Sequence[Answer],
                    ref, metric: str) -> list[Answer]:
    """The reference computed in bfloat16 in the program's place: its own
    models of the same configurations, scored at that precision."""
    import jax.numpy as jnp

    out = []
    for a in answers:
        model = ref.fit(prep, a.family, a.params, jnp.bfloat16)
        score = ref.score(metric, y_valid,
                          ref.probs(a.family, model, x_valid, jnp.bfloat16))
        out.append(dataclasses.replace(a, model=model, score=score))
    return out


def worst(per_answer: Sequence[Mapping[str, float]]) -> dict[str, float]:
    out: dict[str, float] = {}
    for d in per_answer:
        for k, v in d.items():
            out[k] = max(out.get(k, 0.0), v)
    return {k: out[k] for k in NUMBERS if k in out}


def judge(values: Mapping[str, float], limits: Mapping[str, float]
          ) -> tuple[bool, dict[str, dict[str, float]]]:
    """(every number within its limit, {name: {"value", "limit"}}). Only
    the numbers the configuration gives a limit are compared: a number
    whose control readings do not stand clear of the program's has none."""
    checks, ok = {}, True
    for name, v in values.items():
        if name not in limits:
            continue
        lim = float(limits[name])
        checks[name] = {"value": float(v), "limit": lim}
        ok &= float(v) <= lim
    return ok, checks
