"""Programs the set-up compiled and wrote to the persistent cache: the
program's ``compile.backend`` records with ``outcome`` ``miss`` that
ended before the traced window's ``fit`` span began
(``harness/compile_spans.py``). 0 where the cell started warm."""

from benchmarks.harness import compile_spans


def read(ctx):
    setup = compile_spans.of_setup()
    return None if setup is None else setup.count("compile.backend",
                                                  "miss")
