"""Plain references, found by name as files.

A configuration names its reference with ``"reference": "<name>"``
(``<name>.py`` here; ``gbdt`` where it names none), and a traffic mix may
name another, which then stands for its cells (a scoring mix over a training
configuration names ``forest_walk``). A reference imports nothing of the
program and takes nothing the program has made.

What the runner of kind ``train`` (``harness/train.py``) takes from a
reference module, and nothing else:

- ``Params.from_dict(params)``: the configuration's parameters as the
  reference reads them;
- ``init_score(y)``: the score boosting starts from;
- ``loss(scores, y)``: the objective's loss of raw scores, in float64, which
  ``check.compare`` is given;
- ``Reference(X, y, params, gh_dtype=, drop_odd_rows=, freeze_scores=)`` with
  ``step()`` (one boosting iteration, returns the training rows' raw
  scores), ``predict_raw(X)``, ``trees`` and ``seconds``; the three keywords
  are the controls: gradients and hessians rounded to ``gh_dtype``, every odd
  row's gradient left out, a step that leaves the scores unchanged.

What the runner of kind ``score`` (``harness/score.py``) takes:

- ``Forest.from_model_text(text, leaf_dtype=, drop_last_trees=)`` with
  ``predict_raw(X)`` (float64 raw scores of raw float32 rows), ``hops`` (the
  nodes the last ``predict_raw`` visited), ``trees`` and ``leaves``; the two
  keywords are the control and the fault: leaf values rounded to
  ``leaf_dtype``, the last trees left out.
"""
