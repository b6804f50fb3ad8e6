"""The share of the router's choices that are identity ("zero-compute")
experts, in per cent: of the target tokens' top-k choices over the checked
steps and the layers, those with an id past the real experts — the
program's own `routing_choices`, read after the window
(`routing_choice_shares` in the run's counters). A third under uniform
choices on LongCat-Flash's router (256 of 768 outputs). None on a run that
counted no choices. Layer: Model."""


def compute(spans, trace, counters):
    shares = counters.get("routing_choice_shares") or {}
    if "zero_choice_share" not in shares:
        return None
    return 100.0 * shares["zero_choice_share"]
