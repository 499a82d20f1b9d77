"""Mean number of live decode slots per burst (``Engine.act`` when the
burst starts), over the bursts of the window."""


def read(name, r):
    slots = r.get("slots")
    if not slots:
        if r.get("rounds_s"):
            raise RuntimeError(f"{name}: rounds in the window but no decode "
                               f"burst seen: Probe no longer wraps the "
                               f"engine's burst (Engine._burst)")
        return None
    return sum(slots) / len(slots)
