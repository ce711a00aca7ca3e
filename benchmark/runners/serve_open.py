from benchmark.runners import serve


def run(ctx):
    """Open loop: requests are due on a seed-made schedule at a fixed rate."""
    return serve.run(ctx, open_loop=True)
