"""Mean, over the requests due in the window, of each request's time per
output token after its first: (last token - first token) / (tokens - 1)."""


def read(run):
    per = [(t.token_times[-1] - t.token_times[0]) / (len(t.token_times) - 1)
           for t in run.rec.measured()
           if t.outcome == "finished" and len(t.token_times) > 1]
    return 1000.0 * sum(per) / len(per) if per else None
