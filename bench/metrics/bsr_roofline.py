"""The BSR kernel's share of its roofline over the window: the least time
the chip needs for every BSR call the window made (bench/work.py, each
call bound by the int8 peak or by HBM bandwidth, whichever is slower)
over the device time of the kernel's events.

Decode steps count their active lanes as rows, prefills their prompt's
tokens: padding lanes and padded prompt positions are not work."""
from bench import work


def read(run):
    if run.device is None or run.spans is None:
        return None
    t = run.device.kernel_time()
    if t <= 0:
        return None
    conf, peak = run.cell.conf, run.peak
    prompt = {p.rid: len(p.prompt) for p in run.planned}
    least = 0.0
    for name, s, e, args in run.spans:
        if name == "decode_step":
            least += work.bsr_pass_least_time(conf, int(args["n_active"]),
                                              peak)
        elif name == "prefill":
            least += work.bsr_pass_least_time(conf, prompt[args["rid"]],
                                              peak)
    return 100.0 * least / t if least > 0 else None
