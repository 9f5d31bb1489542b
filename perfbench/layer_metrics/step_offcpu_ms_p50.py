"""Time the stepping thread was off the CPU outside ``infer.decode.wait``,
a plain decode step (a decode dispatched, nothing prefilled): the step's
wall time less its wait, less the CPU time the thread had there (the
record's ``cpu_s`` less ``wait_cpu_s``, ``time.thread_time()`` at the
edges). Inside the wait the thread is meant to block; outside it, time
off the CPU is the thread that feeds the chip having lost the interpreter
or the core. Read as the median over blocks of 64 consecutive plain steps
of the block's mean, not over single steps: the thread's CPU clock ticks
every 10 ms on the chip's host, where one step reads 0 or a whole tick.
``None`` for a program whose records lack ``cpu_s``."""


def read(run):
    from perfbench import flightlog, steplog

    steps = steplog.window_steps(run)
    return flightlog.step_offcpu_ms_p50(steps) if steps else None
