"""Share of the DSC roofline of the isolated executor call, in %: the
least time its compulsory work needs on this chip (bench/work.py,
bench/peaks.py) over its device time in the trace."""
from bench import work

NEEDS_ISOLATED = True


def read(run):
    call = run.isolated.get("dsc")
    if call is None or run.peak is None:
        return None
    return 100.0 * work.roofline_seconds(call["work"], run.peak) / call["device_s"]
