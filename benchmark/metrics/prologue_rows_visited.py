"""Engine host prologue, the positions it visited: per wave, the sum of the
``rows`` the ``scheduler.pack`` spans carry (every position where the
prologue walks the batch, the moved positions where it diffs the batch
against the armed one; a pass that tried a diff, then walked, counts both),
the median over the waves. Nothing where no ``scheduler.pack`` carries
``rows`` (the identity path has no prologue)."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("rows")
        if s["name"] == "scheduler.pack" else None)
