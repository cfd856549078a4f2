"""Entries the busiest chip owns over the entries its gather kernel walks,
pad bags included, summed over the window batches' dispatch spans (``owned``,
``walked``), in %: 25% on four chips when every chip walks every entry."""


def read(ctx):
    spans = [s[3] for s in ctx.spans
             if s[0] == "dispatch" and s[3].get("batch", -1) >= 1]
    if not spans or any(s.get("owned") is None or not s.get("walked")
                        for s in spans):
        return None
    return 100.0 * sum(s["owned"] for s in spans) / sum(s["walked"] for s in spans)
