"""span-hygiene clean fixture: with-only spans, fabric-routed hops,
remote_session's object API, and a justified suppression."""

from matrixone_tpu.utils import motrace


def balanced(work):
    with motrace.span("balanced", kind="fixture"):
        return work()


def nested(work):
    with motrace.root_span("fixture.root"):
        with motrace.span("inner"):
            return work()


def chunks(source):
    # the span wraps the work between two yields, never the yield
    for raw in source:
        with motrace.span("chunk"):
            text = raw.decode()
        yield text


def server_side(header, dispatch):
    # remote_session is exempt from the with-only factory rule: the
    # session object carries attach()/harvest() by design
    rs = motrace.remote_session(header, proc="cn", name="cn.op")
    with rs:
        resp = dispatch(header)
    rs.attach(resp)
    return resp


def fabric_hop(client, header):
    # no inject here: RpcClient.call threads the ambient ctx itself
    return client.call(header)


def justified(client, header):
    # molint: disable=span-hygiene -- fixture: proves a justified
    # suppression is honored for a deliberate out-of-fabric injection
    motrace.inject(header)
    return client.call(header)
