"""One reader per metric, found by name.

``metrics/<name>.py`` (or, for a metric split by cell as
``<quantity>.<split>``, ``metrics/<quantity>.py``) defines
``read(run) -> float | None``, where ``run`` is the harness's
:class:`chipbench.harness.RunData`. A reader that finds nothing to read
returns None, and the harness leaves the metric out of the line.
"""
