"""Mean KV pages in use (allocator) over the window's decode
iterations: the average KV size of the search."""


def read(ctx):
    pages = ctx["kv_pages"]
    return sum(pages) / len(pages) if pages else None
