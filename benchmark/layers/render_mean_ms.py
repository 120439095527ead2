"""HTTP front end: the answer rendered and written (``http.render``:
Prometheus JSON shape, ``json.dumps``, socket write), per query."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "http.render")
