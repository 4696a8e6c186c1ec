"""Device time of the decode rounds' K and V row writes in the traced window, in ms a round: the paged_row_write custom calls (ops/attention.py: each live lane's page of a layer's two pool leaves copied into VMEM, its new row set and the page copied back; on EVA a second call a layer writes the summaries of the lanes whose token fills a chunk) summed over the window, over the calls of the decode program (step_fn). Nothing on a program without such a call, where XLA's scatter writes the rows."""

KERNEL = "paged_row_write"


def read(c):
    tr = c.get("trace") or {}
    t = sum(v for k, v in tr.get("op_time_s", {}).items() if KERNEL in k)
    calls = sum(v for k, v in tr.get("module_calls", {}).items()
                if "step_fn" in k)
    if not t or not calls:
        return None
    return 1000.0 * t / calls
