"""Share of the sharded engine's all_to_all slots that carried a key in
the window's adds (%): the program's counters ``sharded.keys{op=add}``
over ``sharded.exchange_slots{op=add}`` (n_dev^2 x capacity a call).
Silent where the program keeps no such counters."""


def read(ctx):
    c = ctx.counters
    slots = c.get("sharded.exchange_slots{op=add}", 0)
    if not slots:
        return None
    return 100.0 * c.get("sharded.keys{op=add}", 0) / slots
