"""The device's idle time under the spans of an iteration's decode half
(`decode_inputs`, `decode`, `sample`, `emit` and what lies inside them),
from the trace: the ceiling of what overlapping the next iteration's
host work with the running step could hide. Its parts by sub-span are
`lib/host_share.py:idle_by_span`'s; `token_read_idle_ms_per_iter` is one."""

from lib import host_share


def read(run):
    return host_share.idle_ms_per_iter(run, host_share.on_decode_path,
                                       "decode")
