from benchmark.runners import serve


def run(ctx):
    """Closed loop: each client sends its next request when the last one is done."""
    return serve.run(ctx, open_loop=False)
