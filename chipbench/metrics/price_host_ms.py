"""Milliseconds per plan request of the sharded price loop's host work
besides its waits: copies, dispatch and numpy."""


def read(s):
    from chipbench import spans

    return spans.price_host_ms(s.spans, s.requests)
