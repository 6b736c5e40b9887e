"""Device time, in ms per execution, of one program in the traced window
(``bench.trace_reduce``: the self time of its ops, executions wholly inside
the window), or of that program's ops in one named ``scope``. Nothing
where the program did not run there, or ran no op in that scope."""


def read(run, config, peaks, program: str, scope: str = None):
    prog = (run.trace or {}).get("programs", {}).get(program)
    if not prog:
        return None
    return prog["device_ms"] if scope is None else prog["scope_ms"].get(
        scope)
