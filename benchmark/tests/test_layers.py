"""Device time by layer: the ``tf_op`` paths of a trace's device operations
(``xplane_meta``) and the two readers that charge them to the solver's
``sart.laplacian`` and ``sart.sweep`` scopes."""

import gzip
import os
import shutil

import pytest

from benchmark import profile_trace as T
from benchmark import spec, xplane_meta as X

HERE = os.path.dirname(os.path.abspath(__file__))


def _reader(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                         name + ".py"))


LAP = _reader("laplacian_ms_per_iteration")
KERNEL = _reader("sweep_kernel_roofline")


class _Frame:
    def __init__(self, index, iterations):
        self.index, self.iterations, self.phase = index, iterations, "trace"


# window 0..100 ns: the Laplacian 0-30; the sweep 30-65 (two overlapping
# ops) and 95-100 of an op that runs past the window; a select under
# neither scope; ops wholly before and after the window
W = "jit(run)/while/body/closed_call/while/body/"
OPS = [
    (-20, -5, W + "sart.laplacian/gather:"),
    (0, 10, W + "sart.laplacian/gather:"),
    (10, 30, W + "sart.laplacian/scatter-add:"),
    (30, 60, W + "sart.sweep/pallas_call:"),
    (55, 65, W + "sart.sweep/dot_general:"),
    (65, 70, W + "select_n:"),
    (95, 120, W + "sart.sweep/pallas_call:"),
    (200, 220, W + "sart.laplacian/scatter-add:"),
]
FRAMES = [_Frame(16, 2), _Frame(17, 3)]


def test_layer_is_the_innermost_scope():
    assert X.layer_of(W + "sart.laplacian/gather:") == "sart.laplacian"
    assert X.layer_of(W + "sart.sweep/while/body/sart.laplacian/gather:") \
        == "sart.laplacian"
    assert X.layer_of(W + "sart.laplacian/x/sart.sweep/dot_general:") \
        == "sart.sweep"
    assert X.layer_of(W + "select_n:") is None
    assert X.layer_of("") is None


def test_scope_time_is_clipped_and_merged():
    assert X.scope_ns(OPS, (0, 100), "sart.laplacian") == 30
    assert X.scope_ns(OPS, (0, 100), "sart.sweep") == 40
    assert X.scope_ns(OPS, (0, 20), "sart.laplacian") == 20


@pytest.mark.parametrize("reader,args,want", [
    (LAP, (FRAMES,), 30e-6 / 5),
    (KERNEL, (20e-9,), 50.0),
])
def test_readers_on_synthetic_events(reader, args, want):
    assert reader.reading(OPS, (0, 100), *args) == pytest.approx(want)
    # a program traced without the scopes gives nothing to read
    bare = [(s, e, W + "fusion:") for s, e, _ in OPS]
    assert reader.reading(bare, (0, 100), *args) is None


@pytest.mark.parametrize("reader", [LAP, KERNEL])
def test_readers_without_a_trace(reader):
    class Run:
        trace = None

    assert reader.read(Run) is None


def test_kernel_share_charges_what_sweep_roofline_charges():
    """Where the sweep is all the chip does, the kernel's share is the
    whole loop's."""
    class Outcome:
        mesh, storage_dtype = (1, 1), "float32"

    class Cell:
        config = {"npixel": 6144, "nvoxel": 524288}

    class Run:
        outcome, cell, device_kind = Outcome, Cell, "TPU v5 lite"
        trace = T.TraceData(window=(0, 10**9), chips={
            "A": [(0, 500_000_000, "%sart.sweep.13 custom-call")]})

        @staticmethod
        def frames(phase):
            return [_Frame(16, 4), _Frame(0, 3)]  # frame 0 adds a guess

    roofline = _reader("sweep_roofline").read(Run)
    ops = [(0, 500_000_000, W + "sart.sweep/pallas_call:")]
    got = KERNEL.reading(ops, Run.trace.window, KERNEL.least_seconds(Run))
    assert got == pytest.approx(roofline)


def _unpack(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "host.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", name)) as f:
        with open(path, "wb") as out:
            shutil.copyfileobj(f, out)
    return str(path)


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    """Two warm chains of an earlier 12288 x 262144 configuration on a 2-D
    grid, traced on one v5e before the solver named its scopes."""
    return _unpack("wide_trace.xplane.pb.gz", tmp_path_factory)


def test_tf_op_paths_of_the_unscoped_trace(unscoped):
    paths = {T.short_name(n): p for n, p in X.tf_ops(unscoped).items()}
    assert paths["%body.13 custom-call tpu_custom_call"].endswith(
        "/pallas_call:")
    data = T.load(unscoped)
    fusions = [n for n, _ in T.top_ops(data) if n.endswith(" fusion")][:2]
    assert sorted(paths[n].rsplit("/", 1)[1] for n in fusions) == [
        "gather:", "scatter-add:"]
    # the kernel, the scatter and the gather are nearly all the chip did
    chip = "/device:TPU:0"
    ops = X.scoped_ops(unscoped)[chip]
    hits = [(s, e) for s, e, p in ops
            if p.endswith(("/pallas_call:", "/scatter-add:", "/gather:"))]
    covered = T.total(T.clip(T.merge(hits), *data.window))
    assert covered / T.busy_ns(data, chip) > 0.999


# the traced frames of the run that wrote ``wide_layers_trace``: [index,
# iterations] (seed 3141592653, one v5e)
LAYERS_FRAMES = [(72, 4), (73, 2), (74, 4), (75, 5), (76, 5), (77, 5),
                 (78, 6), (79, 6)] + [(i, 6) for i in range(80, 87)] + [
                     (87, 5)]


@pytest.fixture(scope="module")
def layers_run(tmp_path_factory):
    """Two warm chains of ``wide.warm-series`` (6144 x 524288, one v5e),
    traced with the solver's scopes, laid out where the harness keeps a
    run's trace, and the run record its readers get."""
    bench = tmp_path_factory.mktemp("bench")
    path = (bench / ".out" / "trace" / "wide.warm-series" / "plugins"
            / "profile" / "r")
    path.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data",
                                "wide_layers_trace.xplane.pb.gz")) as f:
        with open(path / "host.xplane.pb", "wb") as out:
            shutil.copyfileobj(f, out)

    class Outcome:
        mesh, storage_dtype = (1, 1), "float32"

    class Cell:
        name, bench_dir = "wide.warm-series", str(bench)
        config = {"npixel": 6144, "nvoxel": 524288}

    class Run:
        outcome, cell, device_kind = Outcome, Cell, "TPU v5 lite"
        trace = T.load(str(path / "host.xplane.pb"))

        @staticmethod
        def frames(phase):
            return [_Frame(i, n) for i, n in LAYERS_FRAMES]

    return Run


def test_layer_readings_of_the_scoped_chip_trace(layers_run):
    assert LAP.read(layers_run) == pytest.approx(57.59123661904761)
    assert KERNEL.read(layers_run) == pytest.approx(69.62171240776414)
    assert _reader("sweep_roofline").read(layers_run) == pytest.approx(
        19.611287326688192)
    # the two scopes cover all but a sliver of what the chip did
    ops, window = X.traced_ops(layers_run)
    chip = T.busiest_chip(layers_run.trace)
    covered = sum(X.scope_ns(ops, window, s) for s in X.SCOPES)
    assert covered / T.busy_ns(layers_run.trace, chip) >= 0.97


def test_program_spans_of_the_scoped_chip_trace(layers_run):
    """The program's chain spans sit on the host plane beside the
    harness's, every fetch carrying the serial of its dispatch; no
    program span takes a harness name."""
    import glob
    import warnings

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(layers_run.cell.bench_dir, ".out",
                                     "trace", "*", "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = []
    with warnings.catch_warnings():  # jaxlib's event_stats type
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name, dict(e.stats)) for e in line.events]
    chains = [s["chain"] for n, s in spans if n == "chain.dispatch"]
    assert len(chains) == 2 and len(set(chains)) == 2
    for chain in chains:
        fetch = sorted((s["what"], s.get("part")) for n, s in spans
                       if n == "result.fetch" and s.get("chain") == chain)
        assert fetch == [("denormalize", None), ("transfer", "scalars"),
                         ("transfer", "solution")]
    harness = [s for n, s in spans
               if n in T.HOST_SPANS or n == T.WINDOW_SPAN]
    assert harness and not any(harness)  # the harness's spans carry no args
