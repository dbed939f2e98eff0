"""Share of the (token, choice) pairs routed in the window whose expert
this chip holds: the device's ``expert_load`` over the host's
``expert_assignments``.  6.25 under even routing over 16 ranks."""


def read(r):
    total = r.counters.get("expert_assignments")
    held = r.counters.get("expert_assignments_held")
    return 100.0 * held / total if total and held is not None else None
