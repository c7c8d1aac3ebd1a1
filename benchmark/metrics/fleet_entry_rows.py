"""Rows a wave brought home by phase B, the entry wire (``_fleet_entries``):
per wave, the ``entry_rows`` the ``kernel.fetch`` spans carry. None where no
``kernel.fetch`` carries the attribute."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("entry_rows")
        if s["name"] == "kernel.fetch" else None)
