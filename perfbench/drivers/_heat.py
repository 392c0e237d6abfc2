"""What every driver reads of the program besides its entry point: the
fallback counters (a fallback that ran means the timed path was not the one
the cell names). Copied from `chip_smoke.py:_fallback_counters`."""


def fallback_counters() -> dict:
    import heat_tpu as ht
    from heat_tpu.utils import metrics

    found = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (str(k),))
        elif path and path[-1].endswith("fallbacks") \
                and isinstance(node, (int, float)):
            found[".".join(path)] = node

    walk(ht.runtime_stats(), ())
    for k, v in metrics.counters().items():
        if k.endswith("fallbacks"):
            found["counters." + k] = v
    return found


def fallbacks_total() -> float:
    return float(sum(fallback_counters().values()))
