"""scheduler.batch_occupancy -- layer: scheduler (serve/scheduler.py);
unit %; source: program_counter; moves output_tok_s.

Mean over the window's decode dispatches of the rows in the batch
(``DECODE_DISPATCH batch=``) over ``max_batch``."""

LAYER, UNIT, SOURCE, MOVES = ("scheduler", "%", "program_counter",
                              "output_tok_s")


def read(ctx):
    rows = [a["batch"] for _, _, a in ctx.events.get("DECODE_DISPATCH", [])]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * ctx.engine["max_batch"])
