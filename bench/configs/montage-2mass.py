"""montage-2mass: the Montage mosaic workflow as a task graph with JAX task
bodies, its input images, and the serial reference.

The graph follows Montage's stages: mProjectPP per image, mDiffFit per
overlapping pair, mConcatFit, mBgModel, mBackground per image, mImgtbl, mAdd
and mShrink. Image ``i`` sits at row ``i // cols`` and column ``i % cols`` of
the grid; neighbours overlap by ``overlap_pixels``. The bodies do the image
arithmetic in the configuration's pixel type:

* project: bilinear resampling of the image under its small rotation and
  shift (plane to plane);
* diff_fit: the difference of two projected images over their overlap, and
  the least-squares plane (offset, x and y slopes, in mosaic coordinates)
  through it;
* bg_model: per-image background planes that best explain every pair's
  fitted difference (least squares with a small ridge that fixes the level);
* background: the image minus its plane, where it has data;
* imgtbl: per-image statistics (mean, spread, min, max) of the corrected
  images;
* add: the corrected images co-added into the mosaic, averaged where they
  overlap;
* shrink: the mosaic averaged over blocks of ``shrink_factor`` pixels.

The reference is :func:`serial_run`: the same bodies called one after the
other in topological order, in plain Python, without the executor,
scheduler, store or prefetch. The executor's result has to equal it bit for
bit (the configuration's guarantee). The control runs the reference with
its arithmetic in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

LIMITS = {"mosaic_max_abs_diff": 0.0}     # the guarantee: bit-identical


# ---------------------------------------------------------------- geometry
class Geometry:
    def __init__(self, cfg: dict) -> None:
        self.h, self.w = (int(x) for x in cfg["image_pixels"])
        self.rows, self.cols = (int(x) for x in cfg["grid"])
        self.n = self.rows * self.cols
        if self.n != int(cfg["n_images"]):
            raise ValueError("n_images does not match the grid")
        self.ovy, self.ovx = (int(x) for x in cfg["overlap_pixels"])
        self.sy, self.sx = self.h - self.ovy, self.w - self.ovx
        self.mh = (self.rows - 1) * self.sy + self.h
        self.mw = (self.cols - 1) * self.sx + self.w
        self.origins = [((i // self.cols) * self.sy, (i % self.cols) * self.sx)
                        for i in range(self.n)]
        steps = {"right": (0, 1), "down": (1, 0), "down_right": (1, 1)}
        self.pairs: list[tuple[int, int, str]] = []
        for i in range(self.n):
            r, c = divmod(i, self.cols)
            for kind in cfg["neighbours"]:
                dr, dc = steps[kind]
                if r + dr < self.rows and c + dc < self.cols:
                    self.pairs.append((i, (r + dr) * self.cols + c + dc, kind))
        rot = math.radians(float(cfg["max_rotation_deg"]))
        shift = float(cfg["max_shift_pixels"])
        # fixed per-image pointing: the projection each mProjectPP applies
        self.pointing = np.array(
            [[rot * math.sin(1.7 * i + 0.3), shift * math.cos(2.3 * i),
              shift * math.sin(0.9 * i + 1.1)] for i in range(self.n)],
            np.float32)
        self.f = int(cfg["shrink_factor"])


def make_inputs(cfg: dict, rng: np.random.Generator) -> dict[str, np.ndarray]:
    """One workflow's raw images on the host: a sky gradient shared by the
    mosaic, a background plane of each image's own, stars and noise."""
    g = Geometry(cfg)
    ps = np.asarray(cfg["background_plane_sigma"], np.float32)
    yy, xx = np.mgrid[0:g.h, 0:g.w].astype(np.float32)
    out = {}
    for i, (oy, ox) in enumerate(g.origins):
        a, b, c = rng.standard_normal(3).astype(np.float32) * ps
        sky = 100.0 + 1e-3 * (xx + ox) + 2e-3 * (yy + oy)
        plane = a + b * xx / g.w + c * yy / g.h
        img = rng.standard_normal((g.h, g.w), np.float32)
        img += sky + plane
        stars = rng.integers(0, g.h * g.w, 200)
        img.flat[stars] += rng.exponential(500.0, 200).astype(np.float32)
        out[f"raw{i}"] = img
    return out


# ------------------------------------------------------------------ bodies
def make_bodies(cfg: dict, dtype=jnp.float32, device=None) -> dict:
    """The jitted bodies, computing in ``dtype`` (float32 as configured; the
    control passes bfloat16) on ``device`` (the default device when None).
    Every argument and result is placed on that device, so a body compiles
    once whether its inputs arrive as host arrays, as other bodies' results
    or as the prefetch engine's device copies."""
    g = Geometry(cfg)
    on = SingleDeviceSharding(device or jax.devices()[0])

    def jit(fn):
        return jax.jit(fn, in_shardings=on, out_shardings=on)

    h, w, mh, mw = g.h, g.w, g.mh, g.mw

    def grid():
        return (jax.lax.broadcasted_iota(jnp.float32, (h, w), 0),
                jax.lax.broadcasted_iota(jnp.float32, (h, w), 1))

    @jit
    def project(img, pointing):
        img = jnp.asarray(img, dtype)
        yy, xx = grid()
        th, dy, dx = pointing[0], pointing[1], pointing[2]
        cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
        y0, x0 = yy - cy, xx - cx
        sy = jnp.cos(th) * y0 - jnp.sin(th) * x0 + cy + dy
        sx = jnp.sin(th) * y0 + jnp.cos(th) * x0 + cx + dx
        fy, fx = jnp.floor(sy), jnp.floor(sx)
        wy, wx = (sy - fy).astype(dtype), (sx - fx).astype(dtype)
        iy, ix = fy.astype(jnp.int32), fx.astype(jnp.int32)
        inside = (iy >= 0) & (ix >= 0) & (iy < h - 1) & (ix < w - 1)
        iy, ix = jnp.clip(iy, 0, h - 2), jnp.clip(ix, 0, w - 2)
        v = ((1 - wy) * (1 - wx) * img[iy, ix] + (1 - wy) * wx * img[iy, ix + 1]
             + wy * (1 - wx) * img[iy + 1, ix] + wy * wx * img[iy + 1, ix + 1])
        return jnp.where(inside, v, jnp.zeros((), dtype))

    regions = {"right": ((slice(None), slice(w - g.ovx, None)),
                         (slice(None), slice(0, g.ovx))),
               "down": ((slice(h - g.ovy, None), slice(None)),
                        (slice(0, g.ovy), slice(None))),
               "down_right": ((slice(h - g.ovy, None), slice(w - g.ovx, None)),
                              (slice(0, g.ovy), slice(0, g.ovx)))}

    def _diff_fit(kind):
        ra, rb = regions[kind]

        @jit
        def fit(a, b, origin_b):
            da, db = a[ra], b[rb]
            ok = ((da != 0) & (db != 0)).astype(dtype)
            ny, nx = da.shape
            y = (jnp.arange(ny)[:, None] + origin_b[0]) / mh
            x = (jnp.arange(nx)[None, :] + origin_b[1]) / mw
            basis = jnp.stack([jnp.ones((ny, nx)), x * jnp.ones((ny, 1)),
                               y * jnp.ones((1, nx))]).astype(dtype)
            d = (da - db) * ok
            bw = basis * ok
            ata = jnp.einsum("iyx,jyx->ij", bw, bw).astype(jnp.float32)
            atd = jnp.einsum("iyx,yx->i", bw, d).astype(jnp.float32)
            coef = jnp.linalg.solve(ata + 1e-6 * jnp.eye(3), atd)
            return jnp.concatenate([coef, ok.sum()[None].astype(jnp.float32)]
                                   ).astype(dtype)

        return fit

    diff_fit = {k: _diff_fit(k) for k in regions}

    @jit
    def concat_fit(*fits):
        return jnp.stack(fits)

    inc = np.zeros((len(g.pairs), g.n), np.float32)
    for p, (a, b, _) in enumerate(g.pairs):
        inc[p, a], inc[p, b] = 1.0, -1.0

    @jit
    def bg_model(table):
        A = jnp.asarray(inc, dtype)
        ata = (A.T @ A).astype(jnp.float32) + 1e-3 * jnp.eye(g.n)
        atf = (A.T @ table[:, :3]).astype(jnp.float32)
        return jnp.linalg.solve(ata, atf).astype(dtype)

    @jit
    def background(img, model, i, origin):
        p = model[i]
        yy, xx = grid()
        y = (yy + origin[0]) / mh
        x = (xx + origin[1]) / mw
        plane = (p[0] + p[1] * x + p[2] * y).astype(dtype)
        return jnp.where(img != 0, img - plane, img)

    @jit
    def imgtbl(*imgs):
        rows = []
        for im in imgs:
            ok = im != 0
            n = jnp.maximum(ok.sum(), 1).astype(dtype)
            mean = jnp.where(ok, im, 0).sum() / n
            var = jnp.where(ok, (im - mean) ** 2, 0).sum() / n
            rows.append(jnp.stack([mean, jnp.sqrt(var),
                                   jnp.where(ok, im, jnp.inf).min(),
                                   jnp.where(ok, im, -jnp.inf).max()]))
        return jnp.stack(rows).astype(dtype)

    @jit
    def add(table, *imgs):
        acc = jnp.zeros((mh, mw), dtype)
        cov = jnp.zeros((mh, mw), dtype)
        for i, (im, (oy, ox)) in enumerate(zip(imgs, g.origins)):
            use = (table[i, 3] > table[i, 2]).astype(dtype)
            acc = acc.at[oy:oy + h, ox:ox + w].add(im * use)
            cov = cov.at[oy:oy + h, ox:ox + w].add((im != 0) * use)
        return acc / jnp.maximum(cov, 1)

    f = g.f

    @jit
    def shrink(mosaic):
        ch, cw = (mh // f) * f, (mw // f) * f
        m = mosaic[:ch, :cw].reshape(ch // f, f, cw // f, f)
        return m.mean(axis=(1, 3))

    return {"project": project, "diff_fit": diff_fit,
            "concat_fit": concat_fit, "bg_model": bg_model,
            "background": background, "imgtbl": imgtbl, "add": add,
            "shrink": shrink}


# ------------------------------------------------------------------- graph
def build_graph(cfg: dict, bodies: dict):
    """The workflow's ``TaskGraph`` with the bodies bound to its tasks."""
    from repro.core.dag import TaskGraph
    from repro.core.hints import size_hint, task

    g = Geometry(cfg)
    img_bytes = g.h * g.w * 4
    pointing = g.pointing
    origins = np.array(g.origins, np.int32)
    tg = TaskGraph()
    for i in range(g.n):
        tg.add_data(f"raw{i}", size_bytes=size_hint(img_bytes))

        def proj_fn(i=i):
            return lambda **kw: {f"proj{i}": bodies["project"](
                kw[f"raw{i}"], pointing[i])}

        tg.add_task(f"mProjectPP{i}", inputs=(f"raw{i}",),
                    outputs=(f"proj{i}",), hints=task(io_ratio=1.0),
                    fn=proj_fn())
    for p, (a, b, kind) in enumerate(g.pairs):
        def diff_fn(p=p, a=a, b=b, kind=kind):
            return lambda **kw: {f"fit{p}": bodies["diff_fit"][kind](
                kw[f"proj{a}"], kw[f"proj{b}"], origins[b])}

        tg.add_task(f"mDiffFit{p}", inputs=(f"proj{a}", f"proj{b}"),
                    outputs=(f"fit{p}",), hints=task(io_ratio=1e-5),
                    fn=diff_fn())
    fits = tuple(f"fit{p}" for p in range(len(g.pairs)))
    tg.add_task("mConcatFit", inputs=fits, outputs=("fits",),
                fn=lambda **kw: {"fits": bodies["concat_fit"](
                    *(kw[n] for n in fits))})
    tg.add_task("mBgModel", inputs=("fits",), outputs=("bgmodel",),
                hints=task(io_ratio=0.5),
                fn=lambda **kw: {"bgmodel": bodies["bg_model"](kw["fits"])})
    for i in range(g.n):
        def bg_fn(i=i):
            return lambda **kw: {f"corr{i}": bodies["background"](
                kw[f"proj{i}"], kw["bgmodel"], np.int32(i), origins[i])}

        tg.add_task(f"mBackground{i}", inputs=(f"proj{i}", "bgmodel"),
                    outputs=(f"corr{i}",), fn=bg_fn())
    corr = tuple(f"corr{i}" for i in range(g.n))
    tg.add_task("mImgtbl", inputs=corr, outputs=("imgtbl",),
                hints=task(io_ratio=1e-5),
                fn=lambda **kw: {"imgtbl": bodies["imgtbl"](
                    *(kw[n] for n in corr))})
    tg.add_task("mAdd", inputs=("imgtbl",) + corr, outputs=("mosaic",),
                fn=lambda **kw: {"mosaic": bodies["add"](
                    kw["imgtbl"], *(kw[n] for n in corr))})
    tg.add_task("mShrink", inputs=("mosaic",), outputs=("shrunk",),
                hints=task(io_ratio=1.0 / g.f ** 2),
                fn=lambda **kw: {"shrunk": bodies["shrink"](kw["mosaic"])})
    tg.mark_sink("shrunk")
    return tg


def serial_run(tg, inputs: dict) -> dict:
    """The reference: every body once, in topological order, in plain
    Python."""
    values = dict(inputs)
    for tid in tg.topo_order():
        t = tg.tasks[tid]
        values.update(t.fn(**{n: values[n] for n in t.inputs}))
    return values
