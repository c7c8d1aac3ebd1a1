"""Megabytes of node state a wave uploads to the device-resident node
table: per wave, the ``upload_mb`` the ``estimator.sync`` spans carry."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("upload_mb")
        if s["name"] == "estimator.sync" else None)
