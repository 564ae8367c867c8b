"""span-hygiene fixture: spans opened outside `with`, a span held across
a `yield`, out-of-fabric injection, hand-built trace wire keys.
AST-only."""

from matrixone_tpu.utils import motrace


def leaky(work):
    sp = motrace.span("leaky")           # opened outside `with`
    sp.__enter__()
    try:
        return work()
    finally:
        sp.__exit__(None, None, None)


def chunks(source):
    for raw in source:
        with motrace.span("chunk"):      # held open across the yield
            yield raw.decode()


def forked_propagation(client, header):
    motrace.inject(header)               # injection outside the fabric
    return client.call(header)


def clobbered(client):
    # hand-built "trace" key ships a stale/foreign context
    return client.call({"op": "ping", "trace": ["dead", "beef"]})
