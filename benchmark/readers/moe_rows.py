"""How the held experts were loaded, from the counts the engine fetches with
each dispatch's tokens (``engine.dispatch`` attributes ``moe_rows``: the
(token, choice) pairs that landed on held experts, summed over the expert
layers; ``moe_rows_max``: the busiest held expert's of any layer).

``per_expert``: mean rows a held expert of a layer saw a dispatch.
``max_over_mean``: the busiest expert's rows over that mean, averaged over
the dispatches in which any row landed."""

from benchmark.readers.program_spans import spans


def read(ctx, what):
    found = [s for s in spans("engine.dispatch") or ()
             if "moe_rows" in s.attrs]
    if not found:
        return None
    model = ctx["cell"].config["model"]
    cells = model["num_experts"] * (model["num_layers"]
                                    - model.get("num_dense_layers", 0))
    if what == "per_expert":
        return sum(s.attrs["moe_rows"] for s in found) / (cells * len(found))
    if what != "max_over_mean":
        raise ValueError(f"moe_rows: unknown quantity {what!r}")
    ratios = [s.attrs["moe_rows_max"] * cells / s.attrs["moe_rows"]
              for s in found if s.attrs["moe_rows"]]
    return sum(ratios) / len(ratios) if ratios else None
