"""Record a small trace to keep as a test's fixture (on the chip):

    python3 benchmark/tests/record_trace.py <directory> <traced seconds> <run.py's arguments>

runs ``run.py`` with ``--trace 1``, the traced length set to ``<traced
seconds>`` instead of ``run.TRACE_SECONDS``, and copies the ``.xplane.pb``
into ``<directory>`` before the run deletes it.  ``data/recorded.xplane.pb``
was made so: ``... data 0.05 --workload h2o_q4_mean_by_id4 --rehearse``."""

import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
import trace_reduce  # noqa: E402


def main(directory, seconds, *argv):
    run.TRACE_SECONDS = float(seconds)
    reduce = trace_reduce.reduce

    def keeping(trace_dir):
        os.makedirs(directory, exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(trace_dir), directory)
        return reduce(trace_dir)

    trace_reduce.reduce = keeping
    return run.main(["--trace", "1", *argv])


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
