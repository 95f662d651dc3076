"""The repo's LUBM miniature (``benches/lubm.py``), copied so that the
yardstick does not move with the program, with ``--seed`` mixed in.

Shape: per university 8 departments x (12 full professors + 80 students +
15 courses), 3,785 triples; types are asserted, so no inference is needed.
Not UBA's generator (about 10^5 triples per university).  The seed enters
the hash that picks a graduate student's degree university, so LUBM Q2's
answer changes with it; seed 0 reproduces ``benches/lubm.py`` exactly.
"""

import numpy as np

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
RDF_TYPE = "http://www.w3.org/1999/02/22-rdf-syntax-ns#type"
DEPTS, PROFS, STUDENTS, GRAD_RATIO, COURSES = 8, 12, 80, 4, 15
_H_U, _H_D, _H_ST, _H_SEED = 2654435761, 40503, 97, 2246822519


def generate(config: dict, seed: int, scale=None) -> dict:
    """``{"terms", "s", "p", "o", "domains"}``: N-Triples terms, id columns
    and the constants a traffic file may draw."""
    U = int(scale or config["universities"])
    D, C, F, S = DEPTS, COURSES, PROFS, STUDENTS
    terms, index = [], {}

    def enc(text):
        term = f"<{text}>"
        i = index.get(term)
        if i is None:
            i = index[term] = len(terms)
            terms.append(term)
        return i

    def intern(strings):
        return np.fromiter((enc(t) for t in strings), np.int64, len(strings))

    p_type = enc(RDF_TYPE)
    pred = {n: enc(UB + n) for n in (
        "subOrganizationOf", "memberOf", "advisor", "worksFor",
        "takesCourse", "teacherOf", "undergraduateDegreeFrom")}
    cls = {n: enc(UB + n) for n in (
        "University", "Department", "FullProfessor", "GraduateStudent",
        "UndergraduateStudent", "Course")}

    univ_iri = [f"http://www.University{u}.edu" for u in range(U)]
    dept_iri = [f"http://www.Department{d}.University{u}.edu"
                for u in range(U) for d in range(D)]
    univ = intern(univ_iri)
    dept = intern(dept_iri).reshape(U, D)
    course = intern(
        [f"{dd}/Course{c}" for dd in dept_iri for c in range(C)]
    ).reshape(U, D, C)
    prof = intern(
        [f"{dd}/FullProfessor{f}" for dd in dept_iri for f in range(F)]
    ).reshape(U, D, F)
    stu = intern(
        [f"{dd}/Student{st}" for dd in dept_iri for st in range(S)]
    ).reshape(U, D, S)

    st_idx = np.arange(S)
    grad = st_idx % GRAD_RATIO == 0
    blocks = []

    def block(s, p, o):
        s = np.asarray(s, np.int64).ravel()
        blocks.append((s, np.full(len(s), p, np.int64),
                       np.asarray(o, np.int64).ravel()))

    block(univ, p_type, np.full(U, cls["University"]))
    block(dept, p_type, np.full(U * D, cls["Department"]))
    block(dept, pred["subOrganizationOf"], np.repeat(univ, D))
    block(course, p_type, np.full(U * D * C, cls["Course"]))
    block(prof, p_type, np.full(U * D * F, cls["FullProfessor"]))
    block(prof, pred["worksFor"], np.repeat(dept.ravel(), F))
    block(prof, pred["teacherOf"], course[:, :, :F])
    block(stu, p_type, np.broadcast_to(
        np.where(grad, cls["GraduateStudent"], cls["UndergraduateStudent"]),
        (U, D, S)))
    block(stu, pred["memberOf"], np.repeat(dept.ravel(), S))
    block(stu, pred["advisor"], prof[:, :, st_idx % F])
    block(stu, pred["takesCourse"], course[:, :, st_idx % F])
    block(stu, pred["takesCourse"], course[:, :, (st_idx + 7) % C])
    # degrees: own university when st % 3 == 0 (Q2's triangle closes), else
    # a hash pick over (university, department, student, seed)
    g_st = st_idx[grad]
    mix = (int(seed) * _H_SEED) % (2 ** 32)
    other = (
        _H_U * np.arange(U, dtype=np.uint64)[:, None, None]
        + _H_D * np.arange(D, dtype=np.uint64)[None, :, None]
        + _H_ST * g_st.astype(np.uint64)[None, None, :]
        + np.uint64(mix)
    ) % np.uint64(U)
    deg = univ[other.astype(np.int64)]
    own = g_st % 3 == 0
    deg[:, :, own] = np.broadcast_to(univ[:, None, None], (U, D, int(own.sum())))
    block(stu[:, :, grad], pred["undergraduateDegreeFrom"], deg)

    return {
        "terms": terms,
        "s": np.concatenate([b[0] for b in blocks]),
        "p": np.concatenate([b[1] for b in blocks]),
        "o": np.concatenate([b[2] for b in blocks]),
        "domains": {"university": univ_iri, "department": dept_iri},
    }
