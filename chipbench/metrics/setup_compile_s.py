"""Seconds the serve engine spent compiling, or loading from JAX's
cache, in the run: ``compile_s`` of ``repro.serve.tracing.compiles``,
the process's count of what its ``generate`` calls compiled. Set-up
warms every shape, so all of it is set-up's unless the window compiled
(standard error's ``compiles in window``). A program without that
counter reads None."""


def read(run):
    try:
        from repro.serve.tracing import compiles
    except ImportError:
        return None
    return compiles()["compile_s"]
