"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (trace, %)."""


def read(facts):
    tr = facts.get("trace")
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"] if facts.get("kind") == "prefill" else None
