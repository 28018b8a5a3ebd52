"""Model step: how uneven the routing was — the busiest expert's tokens over
the mean expert's, a mean over the layers, as the step's own metrics report it
(`moe_load_max_over_mean` in `models/gpt.py::loss`, through `train.report`);
the median over the window's steps. 1 is perfect balance; a capacity factor
of 1.25 would have dropped tokens above 1.25."""

import statistics


def read(run):
    window = run["window"]
    records = window.get("step_records") or []
    values = [r["moe_load_max_over_mean"]
              for r in records[window.get("first_window_record", 0):]
              if "moe_load_max_over_mean" in r]
    return statistics.median(values) if values else None
