"""The least the two quota kernels of a wave have to move, counted from the
DEPLOYMENT'S sizes only, so that the counts read the same work whatever
implements it (whether the program pads, gathers or derives an operand
otherwise is its own business; these counts do not follow it).

Admission of a wave: every row of the batch, padded to the most one
admission takes (131,072), reads its namespace (int32) and its demand (R
int64) and writes its verdict (one byte); every namespace's ``remaining``
(R int64) is read once and its admitted demand written once.

The static-assignment ceiling: for every profile slot (a request size in a
namespace with assignments, and the sizes with none) and every member, the
R int64 ``hard`` limits are read and one int32 answer written.

Both are bound by bytes: the integer operations are a compare or a divide
a cell."""

from __future__ import annotations

from .roofline import least_seconds  # noqa: F401  (the readers' one import)

#: the most rows one admission takes (ops.quota.MAX_ADMIT_ROWS, restated:
#: the yardstick does not import the program)
ADMIT_ROWS = 1 << 17


def quota_admit_count(namespaces: int, r: int,
                      rows: int = ADMIT_ROWS) -> dict:
    read = rows * (4 + r * 8) + namespaces * r * 8
    written = rows + namespaces * r * 8
    return {"bytes": read + written, "int_ops": rows * r * 2}


def quota_caps_count(slots: int, members: int, r: int) -> dict:
    read = slots * members * r * 8
    written = slots * members * 4
    return {"bytes": read + written, "int_ops": slots * members * r}


def cell_counts(cfg: dict) -> tuple:
    """(admission, ceiling) counts of a ``quota`` configuration."""
    t = cfg["tenants"]
    r = int(cfg["resource_dims"])
    slots = len(cfg["request_profiles"]) * (int(t["capped"]) + 1)
    return (quota_admit_count(int(t["namespaces"]), r),
            quota_caps_count(slots, int(cfg["clusters"]), r))
