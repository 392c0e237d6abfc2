"""Model step: how unevenly the router loads the HELD experts: the most pairs
any one of them was sent in the traced window over their mean, from the
program's `moe_pairs_by_expert` (summed over layers; the driver hands it on as
one counter an expert). 1 is even. With random weights it stays near 1; with
trained weights it is what a grouped product's padding and a two-chip
exchange's imbalance follow."""


def read(run):
    held = run.config.get("experts_held")
    if not held:
        return None
    sent = [run.counter_delta(f"moe_pairs_expert_{e}", traced=True)
            for e in range(int(held[1]))]
    if any(n is None for n in sent) or not sum(sent):
        return None
    return max(sent) * len(sent) / sum(sent)
