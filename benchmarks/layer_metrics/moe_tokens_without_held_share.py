"""The share of token-layers in which NONE of a token's choices is an
expert held here, in per cent: over the target tokens of the checked steps
and the layers, from the program's own `routing_choices`
(`routing_choice_shares` in the run's counters). Such a token has no row in
the grouped products and its combine reads nothing; 78 % under uniform
choices at 16 held of 768 outputs and top-12. None on a run that counted
no choices. Layer: Model."""


def compute(spans, trace, counters):
    shares = counters.get("routing_choice_shares") or {}
    if "tokens_without_held_share" not in shares:
        return None
    return 100.0 * shares["tokens_without_held_share"]
