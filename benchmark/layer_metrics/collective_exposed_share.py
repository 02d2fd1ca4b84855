"""Trainer, across chips: time in collective operations during which no
other operation runs on that device, as a share of the traced window."""


def read(facts, trace):
    if trace is None or not trace["devices"] or trace["window_s"] <= 0 \
            or facts.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
