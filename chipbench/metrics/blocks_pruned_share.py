"""Share of scanned blocks that SMA pruning skipped: sum of
``ExecStats.blocks_pruned`` over sum of ``blocks_total``, in percent."""


def read(ctx):
    st = [q["stats"] for q in ctx.queries if q["stats"]]
    total = sum(s["blocks_total"] for s in st)
    if not total:
        return None
    return 100.0 * sum(s["blocks_pruned"] for s in st) / total
