"""Pool moves (promotions plus clean and dirty demotions) per 1,000
accesses in the traced window, from the pool's counters."""


def read(facts, red, peaks):
    if not facts["accesses"]:
        return None
    return facts["moves"] / facts["accesses"] * 1000.0
