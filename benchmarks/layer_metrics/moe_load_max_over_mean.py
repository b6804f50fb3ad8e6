"""Imbalance of the routed load over the experts held here: the fullest
held expert's tokens over the mean, per layer, averaged over the layers —
from the program's own routing counts of the checked steps (its pure
function `routing_counts`, read after the window). 1 is even; the grouped
product's time follows the sum, its tile padding the spread. Layer: Model."""


def compute(spans, trace, counters):
    counted = counters.get("routing_counts")
    if not counted:
        return None
    ratios = [max(layer) * len(layer) / sum(layer)
              for layer in counted if sum(layer)]
    return sum(ratios) / len(ratios) if ratios else None
