"""Layer: compiler.  Source: program_span, read in process from the program's
event ring: this is the one reader that imports the program under test
(`thunder_tpu.observability.events()`), because the benchmark's profiler trace
starts long after set-up and the ring is where the compile pipeline's spans are
kept.  The self time of the ring's `compile` spans: `tt.jit`'s and
`TrainStep`'s interpretation, fw/bw split, transforms, lowering and codegen; an
`xla_compile` span nested in one is XLA's time and is not counted (it is in
`setup_xla_s`).  0 where nothing went through the pipeline (a serving engine
builds its programs with plain `jax.jit`).  `None`, never a part sum, where the
ring is full: its oldest events are gone then.  Moves setup_s."""


def self_seconds(events: list) -> float:
    """Seconds inside `compile` spans and outside any `xla_compile` nested in
    them, from B/E events in time order (one stack a thread)."""
    total, stacks = 0.0, {}
    for e in events:
        if e['ph'] not in ('B', 'E') or e['name'] not in ('compile', 'xla_compile'):
            continue
        stack = stacks.setdefault((e['pid'], e['tid']), [])
        if e['ph'] == 'B':
            stack.append(e)
        elif stack and stack[-1]['name'] == e['name']:
            begin = stack.pop()
            us = e['ts'] - begin['ts']
            inside_compile = any(b['name'] == 'compile' for b in stack)
            if e['name'] == 'compile' and not inside_compile:
                total += us
            elif e['name'] == 'xla_compile' and inside_compile:
                total -= us
    return total / 1e6


def read(ctx):
    from thunder_tpu import observability as obs
    events = obs.events()
    if len(events) >= obs.event_buffer_capacity():
        return None
    return self_seconds(events)
