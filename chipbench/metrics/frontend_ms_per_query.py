"""Mean front-end time per query (lowering and planning), the program's
``ExecStats.frontend_s``, over the traced window's queries."""


def read(ctx):
    fe = [q["stats"]["frontend_s"] for q in ctx.queries if q["stats"]]
    if not fe:
        return None
    return 1e3 * sum(fe) / len(fe)
