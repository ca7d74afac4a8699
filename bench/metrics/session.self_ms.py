"""Milliseconds per walked or hop-cache plan that the session spends
outside its walkers and probes: the ``session.execute`` span's self time
(planning, strategy, index conversion); session counters ``execute_ns -
fused_walk_ns - host_walk_ns - hopcache_probe_ns`` over ``walk +
hopcache``."""

KEYS = ("execute_ns", "fused_walk_ns", "host_walk_ns", "hopcache_probe_ns")


def read(record):
    c = record["counters"].get("session", {})
    plans = c.get("walk", 0) + c.get("hopcache", 0)
    if any(k not in c for k in KEYS) or plans <= 0:
        return None
    own = (c["execute_ns"] - c["fused_walk_ns"] - c["host_walk_ns"]
           - c["hopcache_probe_ns"])
    return own / plans / 1e6
