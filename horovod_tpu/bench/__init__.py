"""horovod_tpu.bench — the benchmark campaign plane.

ROADMAP item 4's stated prerequisite: ten PRs of machinery (replay,
two-fabric collectives, overlap/ZeRO-1, paged serving, width fleets)
have never been measured together, because every sweep so far was an
ad-hoc shell loop that one failed point could zero.  This package turns
a sweep into ONE durable session:

* **campaign.py** — a declarative spec (grid over overlap mode x
  gradient bucket size x hierarchical x replay, plus serve axes)
  expanded into points, each run as its own ``bench.py`` subprocess
  and committed atomically into a ``campaign.json`` journal.  A crash,
  an outer kill or an injected abort loses at most the in-flight
  point; restarting with the same spec skips committed points and
  retries degraded ones up to a budget.

Entry points: ``python -m horovod_tpu.bench.campaign --spec SPEC`` or
``python bench.py --campaign SPEC``; ``scripts/perf_report.py`` renders
the journal + the historical record trajectory.
"""

# No eager submodule import: `python -m horovod_tpu.bench.campaign`
# would re-execute an already-imported module (runpy warns), and the
# package must stay importable without pulling the campaign driver in.
__all__ = ["campaign"]
