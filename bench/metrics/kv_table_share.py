"""Share of the KV pool a paged decode step gathers, over the traced
replay: ``table_blocks`` / ``pool_blocks`` of the program's
``ServeReport``, the block-table width each slot's gather reads over the
pool's size in blocks. A program that does not report them gives
nothing."""


def read(run):
    tables = [getattr(r, "table_blocks", None) for r in run.reports]
    pools = [getattr(r, "pool_blocks", None) for r in run.reports]
    if not pools or None in tables or None in pools:
        return None
    return 100.0 * sum(tables) / sum(pools)
