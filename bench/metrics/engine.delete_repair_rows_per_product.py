"""Affected rows each delete-repair product re-derives on the writer,
over the traced span: ``row_products`` over ``n_products`` of the two
delete phases (``Frontend.stats``: ``engine_work``)."""
from harness import counters


def read(run):
    products = counters.work(run, counters.DELETE_PHASES, "n_products")
    if not products:
        return None
    return counters.work(run, counters.DELETE_PHASES, "row_products") \
        / products
