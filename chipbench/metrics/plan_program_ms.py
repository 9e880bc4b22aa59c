"""Device milliseconds of the planner's programs per plan request."""


def read(s):
    from chipbench.trace import program_modules

    launches, seconds = program_modules(s)
    if not s.requests or not launches:
        return None
    return seconds * 1e3 / s.requests
