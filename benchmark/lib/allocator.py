"""One allocator behaviour for every run: glibc's, as every process
starts.

glibc's malloc serves a request of 128 KiB or more by a fresh mmap until
a block that large has been freed once; then it raises its mmap
threshold to that block's size (up to 32 MiB) and serves the next from
the heap. Which of the two a process ends up doing for the host path's
26 MB reply buffers (`Blob._host` -> `np.asarray(jax.Array)`, one fresh
buffer a Get) is decided by the order of its first large frees: left
alone, the same code ran a whole process at 37 ms or at 80 ms a Get, six
runs to four (my chip runs, PR 23, PERF.md). A benchmark cannot be
judged on that coin.

So the threshold is held where every process starts, 128 KiB, by the
same `mallopt` call that the environment variable
`MALLOC_MMAP_THRESHOLD_=131072` makes: setting it at all switches the
adjustment off. Every large buffer is then a fresh mmap and pays its
page faults, in every run. That is dearer than either mode seen with
the threshold left alone (Get 92 and Add 69 ms, against a Get of 37 or
of 80 and an Add of 9: an Add's temporaries pay too), and it is the
state every process of the program as shipped starts in; the cost stays in `rows_per_s` and `get_p95_ms`, where a
program that reuses its buffers will show its gain. The call overrides
whatever the environment set, so nothing inherited is a knob on the
result.

Not glibc: nothing is set, and the first line of the run says so.
"""

import ctypes

M_MMAP_THRESHOLD = -3          # <malloc.h>
MMAP_THRESHOLD = 128 * 1024    # DEFAULT_MMAP_THRESHOLD, glibc malloc.c


def hold_default() -> str:
    """Returns what was done, for the run's first line."""
    try:
        libc = ctypes.CDLL(None)
        libc.gnu_get_libc_version.restype = ctypes.c_char_p
        version = libc.gnu_get_libc_version().decode()
        ok = libc.mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD)
    except (OSError, AttributeError):
        return "not-glibc:unset"
    return f"glibc-{version}:mmap_threshold={MMAP_THRESHOLD}" if ok == 1 \
        else f"glibc-{version}:mallopt-refused"
