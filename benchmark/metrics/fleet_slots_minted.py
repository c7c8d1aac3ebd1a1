"""Placement slots the fleet table added inside the window: the sum of the
``slots_minted`` the ``scheduler.solve`` spans carry (the program counts a
slot where it interns one, ``karmada_tpu_fleet_slots_minted_total``; the
tracer is cleared where the window opens, so every span is the window's).
Has to read 0: the placement table holds the placements users wrote, and a
spread selection is row state."""


def read(ctx):
    minted = [s["attrs"]["slots_minted"] for s in ctx["spans"]
              if s["name"] == "scheduler.solve" and "slots_minted" in s["attrs"]]
    return float(sum(minted)) if minted else None
