"""Print every plane and line of the trace under a directory, with event
counts and first events: look at a trace by hand before trusting a
reduction of it.

    python3 benchmarks/tools/describe_trace.py benchmarks/out/trace/<cell>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from benchmarks.lib import xplane
    path = xplane.find_xplane(argv[1])
    print(path, os.path.getsize(path), "bytes")
    for row in xplane.describe(path):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
