"""``make`` of a copy of ``native/`` for ``server.py``, in a process
group of its own that ends whole when ``server.py`` does:

    python3 benchmarks/make_native.py <work directory> <server.py's pid>

``server.py`` dies by SIGKILL when ``run.py`` dies, and a request for a
signal at the parent's death does not pass on to a process's own
children: a ``make`` that carried it would end alone and leave a
``cc1plus`` compiling with nobody to answer to.  So this process asks
for SIGTERM at ``server.py``'s death and answers it, as it answers a
``make`` that overruns, by killing its whole group: itself, ``make``
and every compiler under it.  It never imports JAX.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys

from server import die_with_parent

MAKE_TIMEOUT_S = 600


def end_group(*_) -> None:
    os.killpg(0, signal.SIGKILL)


def main(argv) -> int:
    work, parent_pid = argv[0], int(argv[1])
    os.setpgid(0, 0)
    signal.signal(signal.SIGTERM, end_group)
    die_with_parent(signal.SIGTERM, parent_pid)
    try:
        return subprocess.call(
            ["make", "-j3", "-C", work, "all", "_retpu_resolve.so",
             "_retpu_wire.so"], timeout=MAKE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        print(f"make overran {MAKE_TIMEOUT_S} s", file=sys.stderr,
              flush=True)
        end_group()
        return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
