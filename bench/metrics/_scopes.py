"""Device time per traced step by program scope and by collective, from
the reduced trace's ``scope_s`` and ``category_s`` (shared by the scope
readers).  Each returns None where the run is of another traffic kind,
was not traced, or its trace holds nothing to read."""

from bench import flops, trace


def _per_step(facts, kind):
    """(reduced trace, traced steps) of a traced ``kind`` run, or None."""
    tr = facts.get("trace")
    if facts.get("kind") != kind or not tr or not facts.get(
            "traced_steps") or not tr["op_s"]:
        return None
    return tr, facts["traced_steps"]


def scope_ms(facts, kind, scope):
    """Device ms per step of the ops under ``scope``."""
    got = _per_step(facts, kind)
    if got is None or scope not in got[0]["scope_s"]:
        return None
    return got[0]["scope_s"][scope] / got[1] * 1e3


def unscoped_share(facts, kind):
    """Share of device op time in no program scope (%)."""
    got = _per_step(facts, kind)
    if got is None:
        return None
    tr = got[0]
    return 100.0 * tr["scope_s"].get(trace.UNSCOPED, 0.0) / sum(
        tr["op_s"].values())


def collective_ms(facts, kind):
    """Device ms per step of collective ops (every family)."""
    got = _per_step(facts, kind)
    if got is None:
        return None
    t = sum(got[0]["category_s"].get(c, 0.0) for c in trace.COLLECTIVES)
    return t / got[1] * 1e3 if t else None


def exscan_us(facts, kind):
    """Device us per scan call: the ops under the scan library's
    ``exscan.<schedule>`` scopes, over one call per MoE layer a step
    (the dispatch ``scan_with_total``)."""
    got = _per_step(facts, kind)
    if got is None or "model" not in facts:
        return None
    t = sum(v for k, v in got[0]["scope_s"].items()
            if k.startswith("exscan."))
    calls = sum(s.use_moe for s in flops.layers(facts["model"]))
    if not t or not calls:
        return None
    return t / got[1] / calls * 1e6
