"""Host seconds of the set-up's load per million rows: the wall time of
``insert`` and ``commit`` over the rows committed, as the program counts
them for every database of the process (``LoadStats``)."""


def read(ctx):
    try:
        from repro.core.database import PROCESS_LOAD
    except ImportError:
        return None
    if not PROCESS_LOAD.rows:
        return None
    return PROCESS_LOAD.load_s / (PROCESS_LOAD.rows * 1e-6)
