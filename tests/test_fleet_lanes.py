"""Mixture-of-Modality fleet: backend lanes (AR text / diffusion stub /
whisper transcription), modality-routed dispatch onto lane-typed
endpoints, cross-lane interleaved drains, the fleet-lock narrowing and
multi-turn-context bugfixes, and sharded large members."""

import os
import subprocess
import sys
import threading
import time

import pytest

ARCH_TEXT = "smollm-360m"
ARCH_IMG = "sd-tiny"
ARCH_AUD = "whisper-tiny"


@pytest.fixture(scope="module")
def fleet():
    from repro.serving.fleet import LocalFleet
    return LocalFleet([ARCH_TEXT, ARCH_IMG, ARCH_AUD], reduced=True,
                      batch=3, gen_tokens=6)


# ---------------------------------------------------------------------------
# lane mechanics
# ---------------------------------------------------------------------------

def test_lane_map_and_modalities(fleet):
    assert fleet.modality_of(ARCH_TEXT) == "text"
    assert fleet.modality_of(ARCH_IMG) == "image"
    assert fleet.modality_of(ARCH_AUD) == "audio"
    # AR-based lanes keep their decode schedulers addressable (back-compat)
    assert ARCH_TEXT in fleet.schedulers and ARCH_AUD in fleet.schedulers
    assert ARCH_IMG not in fleet.schedulers


def test_diffusion_lane_slot_batching_and_determinism(fleet):
    """The denoiser has its OWN batch semantics: slots at different
    denoise depths advance together per step; admission is slot-based;
    images are prompt-deterministic."""
    from repro.serving.fleet import DiffusionLane, DiffusionMember
    lane = DiffusionLane(DiffusionMember("d", batch=2), hw=4, steps=5)
    r1 = lane.submit("a red fox")
    r2 = lane.submit("blue mountain")
    r3 = lane.submit("late arrival")          # overflow: queued, not dropped
    assert lane.pending == 3
    done = lane.step()                        # admit 2, first iteration
    assert not done and len(lane.queue) == 1
    assert list(lane.t_idx) == [1, 1]
    done = lane.step()
    assert list(lane.t_idx) == [2, 2]
    finished = {}
    while lane.pending:
        for job in lane.step():
            finished[job.rid] = job
    assert sorted(finished) == [r1, r2, r3]
    assert all(j.steps_done == 5 for j in finished.values())
    assert all(j.image.shape == (4, 4) for j in finished.values())
    # r3 reused a freed slot and its timing fields are populated
    assert finished[r3].slot in (0, 1)
    assert finished[r3].ttft_ms > 0 and finished[r3].t_done > 0
    # determinism + prompt-sensitivity of the image payload
    out1 = fleet.generate(ARCH_IMG, ["a red fox"])[0]
    out2 = fleet.generate(ARCH_IMG, ["a red fox"])[0]
    out3 = fleet.generate(ARCH_IMG, ["something else"])[0]
    assert out1["image"]["sig"] == out2["image"]["sig"]
    assert out1["image"]["sig"] != out3["image"]["sig"]
    assert out1["lane"] == "image" and out1["tokens"] == []


def test_audio_lane_transcribes_payload_dependent(fleet):
    """The payload is the audio (stub frontend): it enters as per-request
    cross-attention context, so different payloads yield different
    transcripts and identical payloads identical ones."""
    a = fleet.generate(ARCH_AUD, ["transcribe my voice memo"])[0]
    b = fleet.generate(ARCH_AUD, ["transcribe my voice memo"])[0]
    c = fleet.generate(ARCH_AUD, ["a completely different recording"])[0]
    assert a["transcript"] == b["transcript"]
    assert a["transcript"] != c["transcript"]
    assert a["lane"] == "audio" and len(a["tokens"]) == 6


def test_cross_lane_interleaved_drain(fleet):
    """One batch_call carrying text+image+audio payloads drains all three
    lanes under one call, each producing its modality payload."""
    call = fleet.call_fn({"m-t": ARCH_TEXT, "m-i": ARCH_IMG,
                          "m-a": ARCH_AUD})
    payloads = [
        {"model": "m-t", "messages": [{"role": "user", "content": "solve"}]},
        {"model": "m-i", "messages": [{"role": "user", "content": "draw"}]},
        {"model": "m-a", "messages": [{"role": "user",
                                       "content": "transcribe"}]},
    ]
    outs = call.batch_call(None, payloads, [{}] * 3)
    lanes = [o["usage"]["vsr_lane"] for o in outs]
    assert lanes == ["text", "image", "audio"]
    assert "image" in outs[1]["choices"][0]["message"]
    assert "transcript" in outs[2]["choices"][0]["message"]
    assert all(o["usage"]["vsr_service_ms"] > 0 for o in outs)


# ---------------------------------------------------------------------------
# BUGFIX: fleet lock narrowed to submit/bookkeeping
# ---------------------------------------------------------------------------

def test_concurrent_callers_share_the_decode_batch(fleet):
    """The old generate() held the fleet lock across the whole drain, so
    a single long request blocked every concurrent caller.  Now only
    submission locks: a short request submitted mid-drain joins the
    in-flight batch and completes long before the long one."""
    t_done = {}
    a_done = threading.Event()

    def long_caller():
        fleet.generate(ARCH_TEXT, ["a long generation request",
                                   "another long generation"], max_new=64)
        t_done["a"] = time.perf_counter()
        a_done.set()

    def short_caller():
        fleet.generate(ARCH_TEXT, ["quick"], max_new=2)
        t_done["b"] = time.perf_counter()
        t_done["b_a_was_running"] = not a_done.is_set()

    ta = threading.Thread(target=long_caller)
    ta.start()
    time.sleep(0.02)                         # A is mid-drain
    tb = threading.Thread(target=short_caller)
    tb.start()
    ta.join(timeout=60)
    tb.join(timeout=60)
    assert "a" in t_done and "b" in t_done
    assert t_done["b_a_was_running"], \
        "short request waited for the long caller's whole drain"
    assert t_done["b"] < t_done["a"]


# ---------------------------------------------------------------------------
# BUGFIX: multi-turn context reaches generation and usage accounting
# ---------------------------------------------------------------------------

def test_multi_turn_context_feeds_generation_and_usage(fleet):
    """_resolve used to feed only msgs[-1] to the scheduler and count
    prompt_tokens from it — history was silently dropped from both."""
    call = fleet.call_fn({"m": ARCH_TEXT})
    last = "and what about the follow-up question"
    multi = [{"role": "user", "content": "first turn about jax sharding"},
             {"role": "assistant", "content": "some assistant answer"},
             {"role": "user", "content": last}]
    single = [{"role": "user", "content": last}]
    out_multi = call(None, {"model": "m", "messages": multi}, {})
    out_single = call(None, {"model": "m", "messages": single}, {})
    # the joined conversation hashes to a different prompt than the last
    # turn alone, so generation is conditioned on the history
    assert out_multi["choices"][0]["message"]["content"] != \
        out_single["choices"][0]["message"]["content"]
    joined = "\n".join(m["content"] for m in multi)
    assert out_multi["usage"]["prompt_tokens"] == len(joined) // 4
    assert out_multi["usage"]["prompt_tokens"] > \
        out_single["usage"]["prompt_tokens"]


def test_overlong_history_keeps_the_newest_turn(fleet):
    """Truncation of an over-long joined conversation must drop the
    OLDEST history, not the current question: two conversations sharing
    a long history but differing in their final turn must generate
    differently."""
    call = fleet.call_fn({"m": ARCH_TEXT})
    cap = fleet.members[ARCH_TEXT].prompt_cap
    history = [{"role": "user",
                "content": " ".join(f"word{i}" for i in range(2 * cap))}]
    outs = [call(None, {"model": "m", "messages": history + [
                {"role": "user", "content": q}]}, {})
            for q in ("what is the capital of france",
                      "derive the gradient of attention")]
    assert outs[0]["choices"][0]["message"]["content"] != \
        outs[1]["choices"][0]["message"]["content"]


# ---------------------------------------------------------------------------
# sharded large members (model_axis > 1)
# ---------------------------------------------------------------------------

_SHARDED_SNIPPET = """
import jax
assert jax.device_count() == 4, jax.device_count()
from repro.launch.mesh import make_host_mesh
from repro.serving.fleet import LocalFleet
# a one-chip fleet on a four-chip host spans exactly the device it is given
one = make_host_mesh(devices=jax.devices()[1:2])
assert list(one.devices.flat) == jax.devices()[1:2], one.devices
fleet = LocalFleet(["qwen3-moe-235b-a22b"], reduced=True, batch=2,
                   max_seq=64, gen_tokens=4, model_axis=2)
assert dict(fleet.mesh.shape) == {"data": 2, "model": 2}
m = fleet.members["qwen3-moe-235b-a22b"]
shardings = jax.tree.leaves(jax.tree.map(lambda x: x.sharding, m.params))
specs = [tuple(s.spec) for s in shardings]
assert any("model" in str(sp) for sp in specs), specs[:8]
outs = fleet.generate("qwen3-moe-235b-a22b", ["shard me across hosts"])
assert len(outs[0]["tokens"]) == 4, outs
print("SHARDED_OK", sorted({str(sp) for sp in specs})[:4])
"""


def test_model_axis_shards_large_member_across_devices():
    """Fleet construction with a mesh model axis builds the member's
    params/decode state sharded under sharding/rules.py (4 fake host
    devices, 2-way model parallel for the big MoE's reduced shapes)."""
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SNIPPET],
        capture_output=True, text=True, timeout=540, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "SHARDED_OK" in proc.stdout


def test_model_axis_exceeding_devices_raises():
    from repro.launch.mesh import make_host_mesh
    with pytest.raises(RuntimeError, match="model axis"):
        make_host_mesh(model=4096)


def test_host_mesh_takes_the_given_devices():
    import jax
    from repro.launch.mesh import make_host_mesh
    one = jax.devices()[:1]
    assert list(make_host_mesh(devices=one).devices.flat) == one
    with pytest.raises(RuntimeError, match="model axis 2 needs"):
        make_host_mesh(model=2, devices=one)
