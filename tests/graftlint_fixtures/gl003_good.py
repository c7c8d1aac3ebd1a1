"""GL003 good fixture: registered reads (direct and through a module
constant), env writes, and non-prefixed keys. Parsed by graftlint only."""

import os

_FLAG = "KARMADA_TPU_MESH_DEVICES"  # registered in utils/flags.py


def read():
    a = os.environ.get(_FLAG, "")  # OK: registered, via constant
    b = os.getenv("KARMADA_TPU_NO_NATIVE")  # OK: registered, direct
    c = os.environ.get("JAX_PLATFORMS")  # OK: not a KARMADA_TPU_* key
    os.environ["KARMADA_TPU_MESH_DEVICES"] = "4"  # OK: a write, not a read
    return a, b, c
