"""Mean of the harness's span around ``session.sql(text)``: text to
DataFrame. Overrides and stage planning run inside ``collect()`` and have no
boundary yet. Through the endpoint the harness sees no such span."""


def read(ctx):
    spans = [d["sql_lower_s"] for d in ctx["done"] if "sql_lower_s" in d]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
