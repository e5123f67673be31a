"""How unevenly the router loads the experts held: the busiest expert's rows
over the mean, in the sparse layer where that is largest, from the program's
``expert_rows`` counter (rows per held expert and layer over the whole
window). 1.0 is an even load; the grouped product's time follows the sum, its
tiles' fill follows this."""


def read(trace, stats, facts):
    layers = [rows for rows in stats.get("expert_rows") or () if sum(rows)]
    if not layers:
        return None
    return max(max(rows) * len(rows) / sum(rows) for rows in layers)
