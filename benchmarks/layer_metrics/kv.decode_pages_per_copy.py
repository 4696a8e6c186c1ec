"""Live pages that the paged decode kernel's copy chain carried over the window's decode rounds, over the copy descriptors a leaf it started for them: the program span engine.round's kv_pages over its kv_copies (counted on the host from the table rows and lengths by the kernel's own rule, ops.attention.paged_decode_copies: one descriptor for a step of pages whose ids ascend by one, one a page elsewhere). 1 is a copy a page, which is also what a program that gathers reads (every page by its own index); the step's length (16 pages of 8 KiB) is the most. Nothing on a program whose rounds carry no such count."""


def read(c):
    from benchmarks import program_spans as ps

    rs = [r for r in ps.rounds(c) or [] if r.get("kv_copies")]
    copies = sum(r["kv_copies"] for r in rs)
    return sum(r["kv_pages"] for r in rs) / copies if copies else None
