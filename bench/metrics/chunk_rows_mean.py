"""Mean requests per tier chunk, padding rows excluded: the program's
tier counts over its chunk count."""


def read(run):
    chunks = run.served.ingress["n_chunks"]
    return sum(run.served.tier_counts) / chunks if chunks else None
