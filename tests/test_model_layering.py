"""The layering of ``semantic_router_tpu/models/``, read from the source by
``ast``: a file that defines a served ``model_type`` imports no other such
file; what two of them need lives in a parts module, which imports no model
file; no name that crosses a file of ``models/`` is private.  One recorded
exception, ``joyai_llm_flash -> dots3_note`` (below)."""

import ast
import glob
import os

import pytest

MODELS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "semantic_router_tpu", "models")
FILES = sorted(os.path.basename(f)[:-3]
               for f in glob.glob(os.path.join(MODELS, "*.py")))
# the rows of ``runtime.bootstrap.GENERATORS``: one file a served type
SERVED = {"sdar_moe", "lfm2_moe", "dots3_note", "joyai_llm_flash", "laguna",
          "qwen3", "olmo_hybrid"}
# what the generative decoders share; each owns one decision
PARTS = {"experts", "mapped_prefill", "checkpoints", "decoder_parts",
         "cached_model", "latent_attention", "gated_window"}
# the ONE import of a served model's file by another, and the only names it
# may read there: ``chipbench/tests/test_latent_mtp_ar_guard.py`` injects the
# self-drafting guard's ``no_bias`` fault by patching ``dots3_note.route``,
# because ``joyai_llm_flash``'s expert half IS ``dots3_note.moe`` (read at
# call time); a PR that may edit ``chipbench/`` moves the patch point, then
# the DeepSeek-V3-shaped layer both load becomes a parts module (ROADMAP D6)
EXCEPTION = {("joyai_llm_flash", "dots3_note"): {"layer_params", "moe"}}


def tree_of(name):
    with open(os.path.join(MODELS, name + ".py")) as f:
        return ast.parse(f.read())


def siblings_imported(name):
    """``{sibling file: names imported from it}`` over every import of
    ``name.py``, lazy ones inside functions too; ``from . import x`` gives
    ``x`` with the attributes the file then reads of it."""
    tree = tree_of(name)
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom) or node.level != 1:
            continue
        if node.module:  # from .x import a, b
            out.setdefault(node.module.split(".")[0], set()).update(
                a.name for a in node.names)
        else:            # from . import x [as y]
            for a in node.names:
                out.setdefault(a.name, set()).update(
                    n.attr for n in ast.walk(tree)
                    if isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == (a.asname or a.name))
    return out


def test_the_files_are_the_ones_this_test_knows():
    assert SERVED | PARTS <= set(FILES)
    from semantic_router_tpu.runtime import bootstrap

    assert set(bootstrap.GENERATORS) == SERVED


@pytest.mark.parametrize("name", sorted(SERVED))
def test_a_served_model_imports_no_other_served_model(name):
    for other, names in siblings_imported(name).items():
        if other not in SERVED:
            continue
        allowed = EXCEPTION.get((name, other))
        assert allowed is not None, (
            f"models/{name}.py imports models/{other}.py ({sorted(names)}): "
            f"what two served models need lives in a parts module "
            f"({', '.join(sorted(PARTS))}), not in the model that needed "
            f"it first")
        assert names <= allowed, (
            f"models/{name}.py may read only {sorted(allowed)} of "
            f"models/{other}.py (the benchmark's tests patch that family's "
            f"router there; ROADMAP D6), not {sorted(names - allowed)}")


@pytest.mark.parametrize("name", sorted(PARTS))
def test_a_parts_module_imports_no_model_file(name):
    served = set(siblings_imported(name)) & SERVED
    assert not served, (
        f"models/{name}.py is a parts module and imports "
        f"{sorted(served)}: a model file imports its parts, never the "
        f"other way")


@pytest.mark.parametrize("name", FILES)
def test_no_private_name_crosses_a_file(name):
    private = {other: sorted(n for n in names if n.startswith("_"))
               for other, names in siblings_imported(name).items()}
    private = {k: v for k, v in private.items() if v}
    assert not private, (
        f"models/{name}.py reaches for private names of its siblings: "
        f"{private}; a name two files need is public, in the file named "
        f"for it")
