"""Interconnect: the bytes the node-partitioned bitmap layout puts between
chips per committed generation, in MB (10^6 bytes), from the
``truss_exchange_bytes_total`` counter: the payload of each peel wave's
psum of partial supports and of each closure iteration's gather of the
chunk's bitmap rows, computed by the program from the shapes.  A program
without the counter gives nothing."""


def read(ctx):
    exchanged = ctx.counters.get("truss_exchange_bytes_total")
    if exchanged is None or not ctx.generations:
        return None
    return exchanged / 1e6 / ctx.generations
