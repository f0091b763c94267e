#!/usr/bin/env python3
"""One run of one cell: `bng run`'s own loop under load, on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: build the app from the configuration's argv,
fill the tables and build the traffic from the seed, warm up the cell's
own shapes, measure for `--seconds`, drain, check what came out against
the host reference, print one JSON line last. No chip, no result. What
belongs to the deployment (addresses, provisioning, frames, reference) is
the configuration's kit, `benchmark/kits/<name>.py`.

    --sweep r1,r2,...   fixed_rate cells only: one set-up, then --seconds at
                        each data rate (frames/s), to find the knee K
    --control <kind>    a deliberately weakened run that `correct` must
                        fail: stale-binding | bad-checksum
    --slices <s>        also print the window's metrics over each <s> seconds
                        of it (one long run shows what a window length buys)
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CPU_REHEARSAL_MAX_SUBSCRIBERS = 1 << 16
SAMPLE = 4096
KEEP_FRAMES = 1 << 17
CONTROLS = ("stale-binding", "bad-checksum")


def say(msg: str) -> None:
    print(msg, flush=True)


def load_cell(bench_dir: str, name: str):
    from benchmark.lib.app import load_named

    with open(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    return (bench, cell, load_named("configs", cell["config"], bench_dir),
            load_named("traffic", cell["traffic"], bench_dir))


def find_devices(chips: int, subscribers: int):
    """The chips the cell asks for, or no run. The CPU stands in only when
    the environment asks for it by name AND the sizes are a rehearsal's."""
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu":
        rehearsal = (os.environ.get("JAX_PLATFORMS") == "cpu"
                     and subscribers <= CPU_REHEARSAL_MAX_SUBSCRIBERS)
        if not rehearsal:
            raise SystemExit(f"run.py: needs {chips} TPU chip(s); this machine "
                             f"has {len(devs)} {plat} device(s)")
    else:
        with open(os.path.join(ROOT, "benchmark", "lib", "peaks.json")) as f:
            if devs[0].device_kind not in json.load(f):
                raise SystemExit(f"run.py: no published peaks for device kind "
                                 f"{devs[0].device_kind!r} in lib/peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"run.py: needs {chips} chip(s), found {len(devs)}")
    return devs[:chips]


class Loop:
    """The harness's `while`: each beat is push what is due ->
    `app.drive_once()` -> pop everything -> stamp; `app.tick()` once a
    second. Generator and loop share one thread, as `bng run` has one."""

    def __init__(self, app, traffic, seed: int = 0, tamper=None):
        from benchmark.lib import app as applib

        self.app, self.tr = app, traffic
        self.ring = app.components["ring"]
        self.tamper = tamper
        if applib.shape(app) == "cluster":
            self.drops = lambda: int(self.ring.stats()["drop"])
        else:
            self.drops = lambda: app.components["engine"].stats.dropped
        # frames outstanding never exceed what the TX ring can hold: the
        # loop drops a reply it cannot inject (cli.py _drive_scheduler),
        # and a frame lost after the ring accepted it breaks the
        # configuration's guarantee
        share = traffic.mix.get("outstanding_cap_of_ring_depth")
        self.cap = int(self.ring.depth * share) if share else None
        self.idle = lambda: applib.idle(app)
        self.pushed = self.popped = 0
        # fixed_rate: a frame that is due and finds no room (the cap, or the
        # ring) is held and offered again at the next beat, its latency still
        # running from when it was due. `held_max` is the most that waited at
        # once, `unoffered` what was still held when the drain gave up
        self.held_max = self.unoffered = 0
        self.rel_last = 0.0  # the last beat's offer time, frozen by the drain
        # beats in which the cap left no room at all, left less room than
        # was due, and in which the ring itself took less than it was given
        self.push_beats = self.cap_full = self.cap_cut = self.ring_short = 0
        self.foreign = 0  # frames `tick()` put on the TX ring itself (RAs)
        self.drop0 = self.drops()
        self.spans = []  # (t_push, t_drive, t_pop, t_end, pushed, popped)
        self.kept = []  # (t_end, [(frame, flags), ...])
        # a fixed_rate mix keeps every reply (each is timed); a flood keeps
        # every beat's until KEEP_FRAMES are held, then thins what it holds
        # and what it will hold by half, so the sample spans the window
        self.keep_all = not traffic.flood
        self.keep_p, self.kept_n = 1.0, 0
        self.rng = np.random.default_rng([int(seed), 0x6EE])
        self.push_t = None if traffic.flood else np.full(traffic.n, -1.0)
        # jax.profiler.TraceAnnotation while the profiler runs
        self.annot = lambda name: contextlib.nullcontext()
        self.pushing = True

    def outstanding(self) -> int:
        return (self.pushed + self.foreign - self.popped
                - (self.drops() - self.drop0))

    def tick(self, wall: float | None = None) -> None:
        before = self.ring.tx_pending()
        self.app.tick(wall)
        self.foreign += self.ring.tx_pending() - before

    def backlog(self) -> int:
        """Frames that came due while the loop was beating and wait for room."""
        return 0 if self.tr.flood else sum(s.seen - s.at for s in self.tr.streams)

    def _push(self, rel: float, now: float | None = None) -> int:
        """Offer what is due at `rel`; `now` is the time a push is stamped
        with (later than `rel` only in the drain, which offers what is held)."""
        now = rel if now is None else now
        room = (self.cap - self.outstanding()) if self.cap else 1 << 30
        ring, n, due, short = self.ring, 0, 0, False
        acc, net = self.tr.streams
        if not self.tr.flood:
            for s in (acc, net):
                s.seen = int(np.searchsorted(s.due, rel, side="right"))
            due = self.backlog()
        for s in (acc, net):
            if self.tr.flood:
                # a queue that is never empty: top the ring up every beat,
                # the access side's share of the room first, then the rest
                want = (room * len(acc.frames)
                        // (len(acc.frames) + len(net.frames))
                        if s is acc else room - n)
                due = 1 << 30
                got = ring.rx_push_batch(s.frames[s.at:s.at + want],
                                         from_access=s.from_access) if want > 0 else 0
                short = short or got < want
                s.at = (s.at + got) % max(len(s.frames), 1)
                s.sent += got
            else:
                # room too small for all that waits is shared by the sides
                # in proportion to what waits on each
                wait = s.seen - s.at
                take = max(min(wait, room * wait // due if s is acc and due > room
                               else room - n), 0)
                got = ring.rx_push_batch(s.frames[s.at:s.at + take],
                                         from_access=s.from_access) if take else 0
                short = short or got < take
                self.push_t[s.ids[s.at:s.at + got]] = now
                s.at += got
            n += got
        self.held_max = max(self.held_max, self.backlog())
        self.push_beats += 1
        self.cap_full += room <= 0 < due
        self.cap_cut += 0 < room < due
        self.ring_short += short
        self.pushed += n
        return n

    def _pop(self) -> list:
        ring = self.ring
        got = ring.tx_pop_batch()
        while ring.fwd_pending():
            g = ring.fwd_pop()
            if g is None:
                break
            got.append(g)
        if self.tamper is not None and got:
            got = self.tamper(got)
        self.popped += len(got)
        return got

    def beat(self, t_open: float, keep: bool) -> int:
        clock, span = time.perf_counter, self.annot
        t0 = clock()
        with span("bench.push"):
            if self.pushing:
                self.rel_last = t0 - t_open
                n = self._push(self.rel_last)
            else:  # the drain: only what the window left held
                n = self._push(self.rel_last, t0 - t_open) if self.backlog() else 0
        t1 = clock()
        with span("bench.drive_once"):
            moved = self.app.drive_once()
        t2 = clock()
        with span("bench.pop"):
            got = self._pop()
        t3 = clock()
        if keep and got and (self.keep_all or self.rng.random() < self.keep_p):
            self.kept.append((t3 - t_open, got))
            self.kept_n += len(got)
            if not self.keep_all and self.kept_n > KEEP_FRAMES:
                self.kept = self.kept[::2]
                self.kept_n = sum(len(g) for _, g in self.kept)
                self.keep_p /= 2
        self.spans.append((t0, t1, t2, t3, n, len(got)))
        return moved + n

    def run(self, seconds: float, profile=None):
        """Measure for `seconds`; returns the window's true length and
        the clock reading it opened at."""
        t_open = self.t_open = time.perf_counter()
        last_tick = time.time()
        while True:
            now = time.perf_counter() - t_open
            if now >= seconds:
                break
            if profile is not None:
                profile.maybe_start(now)
            moved = self.beat(t_open, True)
            wall = time.time()
            if wall - last_tick >= 1.0:
                last_tick = wall
                self.tick(wall)
            if moved == 0:
                time.sleep(0.001)  # `bng run`'s idle sleep
        window = time.perf_counter() - t_open
        # frames that left with a verdict: popped replies and counted drops
        self.served = (self.popped - self.foreign
                       + self.drops() - self.drop0)
        if profile is not None:
            profile.stop()
        return window, t_open

    def drain(self, t_open: float, keep: bool, limit_s: float = 30.0) -> None:
        """After the window: nothing new comes due; what is held is still
        offered, and the loop beats until everything is out."""
        deadline = time.perf_counter() + limit_s
        quiet = 0
        self.pushing = False
        while time.perf_counter() < deadline:
            self.beat(t_open, keep)
            quiet = quiet + 1 if self.idle() else 0
            if (quiet >= 3 and not self.backlog()
                    and (self.outstanding() <= 0 or quiet >= 50)):
                break
        self.unoffered = self.backlog()


def warm_up(app, traffic, limit_s: float = 600.0) -> None:
    """Every frame of the warm-up pool once through the same loop."""
    loop = Loop(app, traffic)
    t_open = time.perf_counter()
    total = sum(len(s.frames) for s in traffic.streams)
    while loop.pushed < total:
        loop.beat(t_open, False)
        if time.perf_counter() - t_open > limit_s:
            raise SystemExit("run.py: the warm-up did not finish in time")
    loop.drain(t_open, False)
    if loop.outstanding() != 0:
        raise SystemExit(f"run.py: warm-up lost {loop.outstanding()} frames")
    loop.tick()  # the 60 s sweeps fire here, not inside the window
    loop._pop()


class Profile:
    """A `jax.profiler` trace of the window's last seconds."""

    def __init__(self, out_dir: str, start_at: float):
        self.dir, self.start_at = out_dir, start_at
        self.on = False
        self.t_start = self.t_stop = None

    def maybe_start(self, now: float) -> None:
        if self.on or now < self.start_at:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # it would time the tracer, not the loop
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False


def make_tamper(rate: int = 64):
    """bad-checksum control: every `rate`-th frame popped has one byte of
    its IP checksum flipped where the loop takes it off the ring."""
    state = {"n": 0}

    def tamper(got):
        out = []
        for raw, fl in got:
            state["n"] += 1
            if state["n"] % rate == 0:
                raw = raw[:24] + bytes([raw[24] ^ 0x01]) + raw[25:]
            out.append((raw, fl))
        return out
    return tamper


def check(app, kit, traffic, loop, c0: dict, c1: dict,
          seed: int) -> tuple[bool, int, list[str], dict]:
    """`correct`: the counts balance and a seeded sample of what the ring
    gave back is what the kit's reference says. Every number compared is
    printed beside its limit, and returned so ({name: {value, limit}}) for
    the result line; every comparison is exact (limit 0).

    The host's share balances against what the kit's traffic declares: a
    `Traffic` may give `to_host`, a boolean array over frame ids beside
    `is_dhcp`, true for a frame the tables cannot answer by the kit's own
    provisioning (a MAC it did not provision, a flow it did not install).
    What the slow path handled, what the program passed up and what the
    device's responder did not hit each equal the declared frames the ring
    accepted: a punt nobody declared, or a declared one that never reached
    the host, is not correct. No `to_host`: nothing is declared."""
    lines, compared, ok = [], {}, True

    def hold(name: str, value: int, limit: int = 0) -> None:
        nonlocal ok
        lines.append(f"check {name}={value} limit={limit}")
        compared[name] = {"value": int(value), "limit": limit}
        ok = ok and abs(value) <= limit

    def accepted(mask, access_only: bool = False) -> int:
        """The frames of `mask` (over frame ids) that the ring accepted."""
        n = 0
        for s in traffic.streams:
            m = mask[s.ids]
            if not len(m) or (access_only and not s.from_access):
                continue
            if traffic.flood:  # the pool cycles
                n += (s.sent // len(m)) * int(m.sum()) \
                    + int(m[:s.sent % len(m)].sum())
            else:
                n += int((m & (loop.push_t[s.ids] >= 0)).sum())
        return n

    to_host = getattr(traffic, "to_host", None)
    told_dhcp = told_data = 0
    if to_host is not None:
        to_host = np.asarray(to_host, bool)
        told_dhcp = accepted(to_host & traffic.is_dhcp)
        told_data = accepted(to_host & ~traffic.is_dhcp)
        lines.append(f"check declared to the host: {told_dhcp} DHCP, "
                     f"{told_data} data frames accepted")

    lost = loop.outstanding()
    hold("lost_frames", lost)
    dev = {k: c1["device"][k] - c0["device"][k] for k in c1["device"]}
    hold("qos_drops", dev["qos_dropped"])
    hold("counted_drops", loop.drops() - loop.drop0)
    hold("host_slow_path_dhcp",
         c1["host"]["dhcp_handled"] - c0["host"]["dhcp_handled"] - told_dhcp)
    hold("slow_errors", c1["engine"]["slow_errors"] - c0["engine"]["slow_errors"])
    if "passed" in c1["engine"]:
        # a DISCOVER the responder misses and a data frame NAT punts both
        # leave with the verdict PASS (Engine.stats.passed counts either)
        hold("punted_frames", c1["engine"]["passed"] - c0["engine"]["passed"]
             - told_dhcp - told_data)
    hold("dhcp_accepted_minus_device_hits",
         accepted(traffic.is_dhcp, access_only=True) - told_dhcp
         - dev["dhcp_hit"])

    # the sample: every DHCP reply kept, up to half; data fills the rest
    rng = np.random.default_rng([int(seed), 0x5A3])
    flat = [raw for _, got in loop.kept for raw, _ in got]
    tags = [traffic.reply_id(raw) for raw in flat]
    d_idx = [i for i, t in enumerate(tags) if t[0]]
    x_idx = [i for i, t in enumerate(tags) if not t[0]]
    take = list(rng.permutation(d_idx)[:SAMPLE // 2]) if d_idx else []
    rest = SAMPLE - len(take)
    take += list(rng.permutation(x_idx)[:rest]) if x_idx else []
    ref = kit.Reference(app, traffic)
    bad = 0
    seen = dict.fromkeys(ref.kinds, 0)
    first_bad = None
    for i in take:
        raw = flat[i]
        is_d, fid = tags[i]
        good = 0 <= fid < traffic.n and bool(traffic.is_dhcp[fid]) == is_d
        if good:
            seen[is_d] = seen.get(is_d, 0) + 1
            good = ref.holds(fid, raw)
        if not good:
            bad += 1
            first_bad = first_bad or (is_d, fid, raw.hex())
    hold("sampled_replies_differing", bad)
    lines.append("check sample: "
                 + ", ".join(f"{seen[k]} {what}" for k, what in ref.kinds.items())
                 + f", of {len(flat)} kept")
    # a sample without one of the kit's kinds of reply proves nothing of it
    hold("sample_kinds_missing", sum(not seen[k] for k in ref.kinds))
    if first_bad:
        lines.append(f"check first differing reply: dhcp={first_bad[0]} "
                     f"id={first_bad[1]} {first_bad[2][:160]}")
    hold("frames_never_offered", loop.unoffered)
    failed = max(lost, 0) + loop.unoffered + bad
    return ok, failed, lines, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--control", choices=CONTROLS)
    ap.add_argument("--slices", type=float, default=0.0)
    ap.add_argument("--bench-dir", default=os.path.join(ROOT, "benchmark"),
                    help=argparse.SUPPRESS)  # the tests' temporary copy
    args = ap.parse_args(argv)

    try:
        import jax

        from benchmark.lib import app as applib
        from benchmark.lib import layers
        from bng_tpu.telemetry import spans as tele
        from bng_tpu.utils.jaxenv import enable_compilation_cache
    except ImportError as e:
        print(f"run.py: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2

    # every program JAX builds, or loads from its cache, stalls the loop:
    # (clock, seconds) of each, so that a run can say none fell in the window
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: built.append((time.perf_counter(), dur))
        if name.endswith("backend_compile_duration") else None)

    bench, cell, config, mix = load_cell(args.bench_dir, args.workload)
    kit = applib.load_kit(config, args.bench_dir)
    lay = kit.Layout(config, args.seed)
    devs = find_devices(int(cell["chips"]), lay.subscribers)
    on_chip = devs[0].platform == "tpu"
    say(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    say(f"compile cache: {enable_compilation_cache()}")
    say(f"cell: {cell['name']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} control={args.control} "
        f"kit={config.get('kit', applib.DEFAULT_KIT)}")

    t0 = time.time()
    app = applib.build_app(config)
    try:
        n_public = config.get("nat_public_ips", {}).get("count", 0)
        say(f"build: bng run {' '.join(config['argv'])} (+{n_public} public "
            f"IPs; synthetic generator off) in {time.time() - t0:.1f} s")
        prov = kit.provision(app, lay, stale=args.control == "stale-binding")
        resident = sum(x.nbytes for x in applib.table_leaves(app))
        say(f"provisioned: {lay.subscribers} subscribers, {lay.nat_flows} NAT "
            f"flows, {resident} bytes of table leaves; seconds "
            f"{ {k: round(v, 2) for k, v in prov['took'].items()} }")
        say(f"selectors: {applib.selectors(app)}")

        t0 = time.time()
        warm_mix = dict(mix, kind="flood", pool_frames=mix["warmup_frames"],
                        dhcp_share=mix["warmup_dhcp_share"])
        warm = kit.Traffic(warm_mix, lay, prov, app, args.seed, 0.0, stream=1)
        rates = [float(r) for r in args.sweep.split(",") if r]
        if rates:
            if mix["kind"] != "fixed_rate":
                raise SystemExit("run.py: --sweep needs a fixed_rate cell")
            plans = [kit.Traffic(dict(mix, data_rate=r), lay, prov, app,
                                 args.seed, args.seconds, stream=2 + i)
                     for i, r in enumerate(rates)]
        else:
            plans = [kit.Traffic(mix, lay, prov, app, args.seed, args.seconds)]
        say(f"traffic: {sum(p.n for p in plans)} frames built in "
            f"{time.time() - t0:.1f} s")
        t0 = time.time()
        warm_up(app, warm)
        say(f"warm-up: {warm.n} frames and one tick in {time.time() - t0:.1f} s")
        del warm
        gc.collect()
        gc.freeze()  # the frame pool is the benchmark's, not the program's

        if rates:
            for rate, plan in zip(rates, plans):
                loop = Loop(app, plan, args.seed)
                window, t_open = loop.run(args.seconds)
                loop.drain(t_open, True)
                lat = layers.latencies(plan, loop, window)
                say("sweep " + json.dumps({
                    "data_rate": rate, "held_max": loop.held_max,
                    "unoffered": loop.unoffered,
                    "lost": loop.outstanding(),
                    "late_p99_us": layers.pct(lat["late_us"], 99),
                    "offer_p50_us": layers.pct(lat["dhcp_us"], 50),
                    "offer_p99_us": layers.pct(lat["dhcp_us"], 99),
                    "fwd_p50_us": layers.pct(lat["data_us"], 50),
                    "fwd_p99_us": layers.pct(lat["data_us"], 99)}))
            return 0

        plan = plans[0]
        loop = Loop(app, plan, args.seed, tamper=make_tamper()
                    if args.control == "bad-checksum" else None)
        profile = tracer = None
        if args.trace:
            tracer = tele.arm(tele.Tracer(keep_events=1 << 21))
            if on_chip:
                trace_s = min(3.0, args.seconds / 2)
                profile = Profile(os.path.join(ROOT, ".bench_trace",
                                               cell["name"]),
                                  args.seconds - trace_s)
                loop.annot = jax.profiler.TraceAnnotation
        c0 = applib.counters(app)
        setup_s = time.time() - T_START
        try:
            window, t_open = loop.run(args.seconds, profile)
        finally:
            tele.disarm()
        served = loop.served
        loop.drain(t_open, True)
        c1 = applib.counters(app)
        say(f"window: {window:.3f} s, {len(loop.spans)} beats, pushed "
            f"{loop.pushed}, popped {loop.popped}, held at most "
            f"{loop.held_max} at once, never offered {loop.unoffered}, in the "
            f"window {served} left with a verdict")

        late = [d for t, d in built if t_open <= t <= t_open + window]
        say(f"programs built or loaded: {len(built)}, of them in the window "
            f"{len(late)} ({sum(late):.3f} s)")
        sp = np.asarray(loop.spans)[:, :4]
        worst = np.argsort(sp[:, 3] - sp[:, 0])[-3:][::-1]
        say("longest beats: " + "; ".join(
            f"{(sp[i, 3] - sp[i, 0]) * 1e3:.0f} ms at {sp[i, 0] - t_open:.2f} s "
            f"(drive_once {(sp[i, 2] - sp[i, 1]) * 1e3:.0f} ms)" for i in worst))
        say(f"cap on frames outstanding ({loop.cap}): of {loop.push_beats} "
            f"beats it left no room in {loop.cap_full} and less than was due in "
            f"{loop.cap_cut}; the ring took less than it was given in "
            f"{loop.ring_short}")
        correct, failed, lines, compared = check(app, kit, plan, loop, c0, c1,
                                                 args.seed)
        for line in lines:
            say(line)
        ctx = layers.Context(plan=plan, loop=loop, window=window, served=served,
                             c0=c0, c1=c1, tracer=tracer, profile=profile,
                             setup_s=setup_s, n_devices=len(devs))
        for line in layers.summary(ctx, args.slices):
            say(line)
        if args.trace:
            metrics = layers.per_layer(ctx, args.bench_dir, cell["name"])
            if ctx.trace is not None:
                say(f"trace reduced in {ctx.reduce_s:.3f} s: "
                    f"{len(tracer.events)} events in the Tracer's log, idle "
                    f"gaps under {len(ctx.trace['gaps'])} labels")
            if ctx.left_out:
                say("per-layer metrics with nothing to read, left out of the "
                    "line: " + ", ".join(ctx.left_out))
        else:
            metrics = layers.end_to_end(ctx, bench, cell["name"])
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        if on_chip:
            device["memory_peak_bytes"] = max(
                d.memory_stats()["peak_bytes_in_use"] for d in devs)
        result = {"correct": bool(correct), "attempted": loop.pushed + loop.unoffered,
                  "failed": int(failed), "metrics": metrics, "device": device}
        if args.trace and ctx.trace is not None:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
            result["breakdown"] = ctx.trace["breakdown"]
        # what `correct` compared, each beside its limit: last in the line,
        # and the last lines on standard error
        result["compared"] = compared
        say(json.dumps(result))
        for name, c in compared.items():
            print(f"check {name}={c['value']} limit={c['limit']}",
                  file=sys.stderr, flush=True)
        return 0
    finally:
        app.close()


if __name__ == "__main__":
    sys.exit(main())
