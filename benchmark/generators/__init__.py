"""Generators a traffic mix names, found by name as files.

``"data": {"generator": "<name>", ...}`` of a mix names ``<name>.py`` here;
its ``make_table(rows, features, seed, data)`` returns ``(X, y, extra)``:
``X`` float32 ``[rows, features]``, ``y`` float32 ``[rows]`` and a dict of
whatever else a ``Dataset`` of that deployment needs (``categorical_feature``,
``group``, ``weight``), which ``Program.bin`` passes on to ``lgb.Dataset`` by
keyword. A mix that names none gets ``harness/traffic.make_table`` and an
empty dict. The same ``seed`` gives the same table.

``"model": {"generator": "<name>", ...}`` of a scoring mix names a module
whose ``make_model_text(features, rows, seed, model)`` returns the served
model as LightGBM model text.

A generator imports nothing of the program.
"""
