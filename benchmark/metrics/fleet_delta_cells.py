"""Cells a wave's answers came home in on the cell-delta wire: per wave, the
``delta_cells`` the ``kernel.fetch`` spans carry (the wire's ``dtotal``). None
where no ``kernel.fetch`` carries the attribute."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("delta_cells")
        if s["name"] == "kernel.fetch" else None)
