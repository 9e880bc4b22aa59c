"""Milliseconds per plan request that the sharded price loop waited on
the device's partial sums."""


def read(s):
    from chipbench import spans

    return spans.price_wait_ms(s.spans, s.requests)
