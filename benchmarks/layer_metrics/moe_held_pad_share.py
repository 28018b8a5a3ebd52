"""Model step: the share of the rows the held experts' walk took that were
not real — 100 x (1 - `moe_routed_here` / `moe_rows_walked`) of the step's
own metrics (`models/gpt.py::loss`, through `train.report`): the pairs the
router sent to the experts held here beside the static rows of the trips
that carried them (`models/moe.py::_walk`), a mean over the layers that took
a trip, the median over the window's steps. A trip gathers, multiplies,
sorts and sums every row of its static size, so this is the part of the
walk's work that moves nothing. None where the steps report no rows walked
(a model that holds all its experts, a program from before the counter)."""

import statistics


def _of_step(record):
    pairs, rows = record.get("moe_routed_here"), record.get("moe_rows_walked")
    if not isinstance(pairs, list) or not isinstance(rows, list):
        return None
    shares = [1.0 - p / r for p, r in zip(pairs, rows) if r > 0]
    return 100.0 * statistics.fmean(shares) if shares else None


def read(run):
    try:
        window = run["window"]
        records = window.get("step_records") or []
        values = [_of_step(r)
                  for r in records[window.get("first_window_record", 0):]]
        values = [v for v in values if v is not None]
        return statistics.median(values) if values else None
    except Exception:   # noqa: BLE001 — a reader never raises
        return None
