"""Mean over the traced ``run_chains`` calls of the call's wall time less
the device-busy time inside it, in ms: the chain driver's host path."""


def read(rec):
    t = rec.get("trace")
    if t is None or rec["driver"]["kind"] != "chains":
        return None
    rows = [c for c in t["calls"] if c["name"] == "run_chains"]
    if not rows:
        return None
    return 1e3 * sum(c["wall_s"] - c["busy_s"] for c in rows) / len(rows)
