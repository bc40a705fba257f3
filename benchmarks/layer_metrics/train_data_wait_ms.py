"""Mean time the trainer's loop waited for a converted batch (ms): the
process-global StatItem of the ``train/data_wait`` span (utils/stats.py),
total / count. The train driver hands the readers no program counters,
so this reads the process's own item: it covers every step of the
process, the three checked steps of set-up among them, not the window
alone."""


def read(ctx):
    if not (ctx.get("end_to_end") or {}).get("train_tok_s"):
        return None                     # not a training cell's context
    from paddle_tpu.utils.stats import global_stat
    item = global_stat.items().get("train/data_wait")
    if item is None:
        return None
    count, total, _ = item.snapshot()
    return 1e3 * total / count if count else None
