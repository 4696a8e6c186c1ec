"""K and V positions per layer that the window's decode rounds read, over the positions their active slots hold: the program span engine.round's kv_rows_read (a micro-step's reads, from the host registers: the live pages of the active slots where decode attends through the page table, every slot's whole row where it gathers) summed over the rounds that decoded, over their live_tokens. 1 is a round that reads what is live and nothing else."""


def read(c):
    from benchmarks import program_spans as ps

    rs = [r for r in ps.rounds(c) or [] if r.get("kv_rows_read")]
    live = sum(r.get("live_tokens", 0) for r in rs)
    return sum(r["kv_rows_read"] for r in rs) / live if live else None
