"""Executions of the planner's device programs per plan request."""


def read(s):
    from chipbench.trace import program_modules

    launches, _ = program_modules(s)
    if not s.requests or not launches:
        return None
    return launches / s.requests
