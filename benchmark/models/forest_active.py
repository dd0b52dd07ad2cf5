"""``forest`` fit on the population the configuration STATES, not on its
id universe: the served forest for ids far wider than the keys in use.

``_rows.synthetic_rows`` takes a key's share of the traffic as 1 ÷
``key_universe``. Where the universe is the width of the ids (10^16 card
numbers, 2^63 merchant hashes) and not a count of cards, every fitted
count would be 1 and every split degenerate. ``model_params.
key_population`` states the population instead; with
``forest-rf100-d8-exact``'s (2^23, 2^24) the forest is that cell's to the
bit for a seed."""

from __future__ import annotations

from benchmark.models import forest


def build(config: dict, seed: int) -> dict:
    population = config["model_params"]["key_population"]
    return forest.build(dict(config, key_universe=dict(population)), seed)
