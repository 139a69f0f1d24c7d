"""Scheduler loop: the scheduler thread's CPU seconds over its wall seconds,
outside the `*_wait` phases, inside the window: the counter
`sched_host_cpu_seconds_total` (`time.thread_time` at an iteration's begin
and end and around each wait; PR 38) over the `phase_seconds` whose name does
not end in `_wait`. A sound run does not read 100: 64-88 % on the v5e
(PR 38's six cells, PERF.md section 5): the `*_read` phases count as wall
time and may sleep in the copy to the host. A thread held off the CPU
(another process, the machine, the GIL) reads well under its cell's usual
share. Nothing to read where the program does not count it."""


def read(run):
    w = run["window"]
    cpu = w["counters"].get("sched_host_cpu_seconds_total")
    wall = sum(s for k, s in w["phase_seconds"].items()
               if not k.endswith("_wait"))
    if cpu is None or wall <= 0:
        return None
    return 100.0 * cpu / wall
