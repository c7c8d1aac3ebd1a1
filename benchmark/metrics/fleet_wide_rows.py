"""Rows the fleet table carries in its wide form: per wave, the
``wide_rows`` the ``scheduler.solve`` spans carry (rows of the pass whose
previous result has more sites than a row's columns hold, kept in a slot of
the wide table, or Divided rows past a one-byte cell). Nothing where the
program stamps no such attribute."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("wide_rows")
        if s["name"] == "scheduler.solve" else None)
