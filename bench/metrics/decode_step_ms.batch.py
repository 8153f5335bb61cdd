"""Mean time of one fused decode step: the program's own chunk wall
(``ChunkStats.wall_s``) summed over the window's chunks, over their steps."""


def read(run):
    chunks = run.window.chunks_in_window()
    steps = sum(c.steps for c in chunks)
    if steps == 0:
        return None
    return 1000.0 * sum(c.wall_s for c in chunks) / steps
