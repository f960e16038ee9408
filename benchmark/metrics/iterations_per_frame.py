"""iterations_per_frame (program counter): mean of the iteration counts
``DeviceSolveResult.iterations`` reports for the window's frames."""


def read(run):
    frames = run.frames("window")
    if not frames:
        return None
    return sum(f.iterations for f in frames) / len(frames)
