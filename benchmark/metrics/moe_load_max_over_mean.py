"""Expert layer: the busiest expert's tokens over the mean expert's, over
the window: the deltas of ``pio_seq_moe_expert_tokens_total`` by
``expert`` (summed over layers; 1.0 is an even load). The grouped
products run expert after expert, so the ratio bounds nothing on one
chip; across chips it is the straggler's share."""


def read(ctx):
    name = "pio_seq_moe_expert_tokens_total"
    experts = {dict(ls).get("expert") for (n, ls) in ctx["scrape1"]
               if n == name}
    experts.discard(None)
    if not experts:
        return None
    from benchmark import prom

    moved = [prom.delta(ctx["scrape0"], ctx["scrape1"], name, expert=e)
             for e in sorted(experts)]
    mean = sum(moved) / len(moved)
    if mean <= 0:
        return None
    return max(moved) / mean
