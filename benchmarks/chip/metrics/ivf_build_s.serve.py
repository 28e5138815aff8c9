"""ivf_build_s.serve (s; layer: IVF probe and segment scan,
``serve/ivf.py`` ``IVFIndex.build_projected``; moves setup_s). Wall
seconds of the index build (the ``ivf.build`` span: k-means, the
balanced assignment's spill, the segment layout), from the engine's
``index_build_seconds`` gauge; the driver prints the three stages."""


def read(ctx):
    build = ctx.get("ivf_build_s")
    if not build or "build" not in build:
        return None
    return build["build"]
