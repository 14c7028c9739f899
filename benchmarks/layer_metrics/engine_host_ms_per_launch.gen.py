"""Host work inside the rollout engine a decode launch (ms): the self time
of the program's ``engine.step``, ``engine.admit``, ``engine.launch``,
``engine.flush_tables`` and ``engine.drain`` spans over the traced window's
launches. Their ``*.wait`` children (blocking reads) and ``*.dispatch``
children (program calls, which block on this runtime while their output
buffers cannot be allocated) are left out and logged beside the number."""

from harness import spans


def read(run):
    return spans.engine_host_ms_per_launch(run)
