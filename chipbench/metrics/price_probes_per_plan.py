"""Host round trips of the sharded price loop per plan request."""


def read(s):
    from chipbench import spans

    return spans.price_probes_per_plan(s.spans, s.requests)
