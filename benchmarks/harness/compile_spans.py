"""The compile phases of a run's set-up, read from the program's own
records (``deeplearning4j_tpu.compile.compile_spans()``, PR 39):

    compile.trace     jax traces a function to a jaxpr (attr ``fun``);
                      a jit traced inside another's trace lies in the
                      outer one's interval (since the fold, it is in
                      the outer record's ``nested`` and ``nested_s``)
    compile.lower     the jaxpr's conversion to an MLIR module
                      (``fun`` ``jit(<name>)``)
    compile.backend   XLA's compile, or the load of its executable from
                      the persistent cache (``fun``; ``outcome`` hit,
                      miss or uncached; ``retrieval_s`` on a hit)

The program keeps them always, on ``time.perf_counter``, the clock of
the fit drivers' spans. The set-up is every record that ended at or
before the start of the traced window's ``fit`` root: the warm-up
compiled every shape before the window, and the reference compiles
after it. A phase's seconds are the union of its records' intervals,
so a nested trace is counted once.
"""

from benchmarks.harness import fit_spans

PHASES = ("compile.trace", "compile.lower", "compile.backend")


def program_records():
    """The program's phase records; ``None`` where it keeps none (a
    commit from before them) or its ring let some go: the set-up's
    records are the oldest, so they would read low."""
    from deeplearning4j_tpu.compile import persistent

    read = getattr(persistent, "compile_spans", None)
    if read is None or persistent.cache_stats().get(
            "compile_spans_dropped", 0):
        return None
    return read()


class SetUp:
    """The phase records that ended by ``window_start``."""

    def __init__(self, records, window_start):
        self.records = [r for r in records
                        if r["name"] in PHASES and r["end"] <= window_start]

    def named(self, name):
        return [r for r in self.records if r["name"] == name]

    def seconds(self, name):
        """Length of the union of the phase's intervals."""
        return fit_spans.covered(
            [(r["start"], r["end"]) for r in self.named(name)])

    def count(self, name, outcome):
        return sum(1 for r in self.named(name)
                   if r["attrs"].get("outcome") == outcome)

    def by_fun(self, name):
        """[(fun, records, seconds)] of one phase, longest first: where
        the phase's time went."""
        rows = {}
        for r in self.named(name):
            n, t = rows.get(r["attrs"].get("fun"), (0, 0.0))
            rows[r["attrs"].get("fun")] = (n + 1, t + r["end"] - r["start"])
        return sorted(((f, *v) for f, v in rows.items()),
                      key=lambda row: -row[2])


def of_setup():
    """The set-up's phase records; ``None`` where the program keeps
    none or the tracer holds no ``fit`` root to start the window."""
    records = program_records()
    if records is None:
        return None
    try:
        tree = fit_spans.of_window()
    except LookupError:
        return None
    if tree is None:
        return None
    return SetUp(records, tree.root["start"])
